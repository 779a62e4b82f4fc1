"""Random weights from the seed, made by the benchmark on the device.

The benchmark, not the program, makes the weights, so that the plain
reference (``arch/<model_type>.py``) takes nothing the program made.
They are laid out as the program's parameter tree (taken from
``jax.eval_shape`` of its ``init_params``, which allocates nothing) and
made in one jitted call, in the type each leaf is served in.

Each leaf is drawn by its role, read from its name in the tree:

- an embedding table: standard normal, so a token's embedding has unit
  variance; where the configuration ties the output head to it, normal
  over ``sqrt(hidden)``, as the head's matrix would be, and the head
  (``lm_head``, a leaf of its own in the program's tree) is made the
  table's transpose, so that the program serves the tied model;
- a norm scale: ``1 + 0.1 * normal``, so the scale multiply is exercised;
- a bias: ``0.1 * normal``, so the QKV bias path is exercised;
- any other matrix ``[..., fan_in, fan_out]``: ``normal / sqrt(fan_in)``.

A program change that renames these leaves makes the reference's reader
(``arch/<model_type>.py``) fail loudly; it does not change the weights
silently.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int) -> jax.Array:
    """A threefry key from any whole-number seed (64-bit and larger seeds
    included), through numpy's ``SeedSequence``."""
    words = np.random.SeedSequence(seed).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(words, impl="threefry2x32")


EMBED, HEAD = "['embed']['w']", "['lm_head']['w']"


def _draw(key, name: str, shape, dtype, tied: bool):
    z = jax.random.normal(key, shape, jnp.float32)
    if name == EMBED:
        x = z / np.sqrt(shape[-1]) if tied else z
    elif name.endswith("['scale']"):
        x = 1.0 + 0.1 * z
    elif name.endswith("['b']"):
        x = 0.1 * z
    else:
        x = z / np.sqrt(shape[-2])
    return x.astype(dtype)


def make_weights(shapes, seed: int, *, tied: bool = False):
    """A tree shaped like ``shapes`` (a tree of ``ShapeDtypeStruct``),
    drawn from ``seed`` on the default device in one jitted call;
    ``tied`` makes the output head the embedding's transpose."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    names = [jax.tree_util.keystr(p) for p, _ in leaves]

    def make(key):
        out = [_draw(jax.random.fold_in(key, i), n, s.shape, s.dtype, tied)
               for i, (n, (_, s)) in enumerate(zip(names, leaves))]
        if tied:
            head = names.index(HEAD)
            out[head] = out[names.index(EMBED)].T.astype(out[head].dtype)
        return jax.tree_util.tree_unflatten(treedef, out)
    return jax.block_until_ready(jax.jit(make)(seed_key(seed)))
