"""PartitionSpec rules: FSDP x TP x EP over the production mesh.

Parameters are sharded 2-D (Megatron TP on the ``model`` axis + FSDP on the
``data`` axis, optionally ("pod","data") for >=100B models); the stack axis
added by layer-scanning is never sharded. Every rule is divisibility-guarded:
a dimension that does not divide by its mesh axis falls back to replication
(e.g. 40 attention heads on a 16-way model axis -> the head matmul columns
shard, the per-head activations replicate; XLA inserts the reshard).

Batch specs are computed per shape cell (``batch_spec``): the largest subset
of data axes whose product divides the global batch is used — long_500k with
global_batch=1 therefore replicates batch and shards the KV-cache sequence
dim instead (``kv_cache_spec``).

Serving adds a third spec family: the paged KV POOL (``kv_pool_spec``) —
the physical word-addressable pool that backs the multi-port serving
engine. Its word axis IS the sequence/page axis (word ``w`` belongs to page
``w // page_tokens``), and it shards across the ``kv`` mesh axis with
PAGE-ALIGNED boundaries: every shard holds a whole number of pages, so a
page never straddles devices and the page tables (host-side python ints)
stay replicated control plane. ``kv_shard_plan`` is the validated geometry
(shards, pages/words per shard) both the pool's device-aware allocator and
the launchers consume.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Any, Optional

import jax
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig

PyTree = Any


@dataclasses.dataclass(frozen=True)
class Rules:
    """Axis assignment for one run."""
    tp: str = "model"                       # tensor/expert-parallel axis
    fsdp: tuple[str, ...] = ("data",)       # parameter/optimizer sharding axes
    dp: tuple[str, ...] = ("data",)         # batch axes (pod included if present)

    @staticmethod
    def for_mesh(mesh: Mesh, *, fsdp_over_pod: bool = False) -> "Rules":
        axes = mesh.axis_names
        if "pod" in axes:
            return Rules(tp="model",
                         fsdp=("pod", "data") if fsdp_over_pod else ("data",),
                         dp=("pod", "data"))
        return Rules()


def _axsize(mesh: Mesh, axes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


def _fit(mesh: Mesh, axes, dim: int):
    """axes if dim divides by their product, else None (replicate)."""
    if axes is None:
        return None
    size = _axsize(mesh, axes)
    if size > 1 and dim % size == 0:
        return axes if isinstance(axes, str) else tuple(axes)
    # try shrinking a tuple of axes from the left (drop 'pod' first)
    if not isinstance(axes, str) and len(axes) > 1:
        return _fit(mesh, axes[1:], dim)
    return None


# ---------------------------------------------------------------------------
# parameter specs
# ---------------------------------------------------------------------------

_COL = re.compile(r"(attn/(wq|wk|wv)|ffn/(w_gate|w_up)|shared/(w_gate|w_up)|"
                  r"tm/(wv|wg)|cm/wk|in_z|in_x)/w$")
_ROW = re.compile(r"(attn/wo|ffn/w_down|shared/w_down|tm/wo|cm/wv|out_proj)/w$")
_REP_OUT = re.compile(r"(tm/(wr|wk)|cm/wr|in_B|in_C|in_dt)/w$")
_MOE_COL = re.compile(r"moe/(w_gate|w_up)$")
_MOE_ROW = re.compile(r"moe/w_down$")


def _n_stack(path: str) -> int:
    if path.startswith("groups/"):
        return 2
    if path.startswith(("layers/", "tail/")):
        return 1
    return 0


def _base_spec(path: str, shape, mesh: Mesh, r: Rules):
    nd = len(shape)
    if path == "embed/w":                       # [V, d]: d-sharded lookup
        return (_fit(mesh, r.fsdp, shape[0]), _fit(mesh, r.tp, shape[1]))
    if path == "lm_head/w":                     # [d, V]: column-parallel
        return (_fit(mesh, r.fsdp, shape[0]), _fit(mesh, r.tp, shape[1]))
    if _MOE_COL.search(path):                   # [E, d, f]
        return (_fit(mesh, r.tp, shape[0]), _fit(mesh, r.fsdp, shape[1]), None)
    if _MOE_ROW.search(path):                   # [E, f, d]
        return (_fit(mesh, r.tp, shape[0]), None, _fit(mesh, r.fsdp, shape[2]))
    if path.endswith("router/w"):               # [d, E]
        return (_fit(mesh, r.fsdp, shape[0]), None)
    if _COL.search(path):                       # [d, out]: column-parallel
        return (_fit(mesh, r.fsdp, shape[0]), _fit(mesh, r.tp, shape[1]))
    if _ROW.search(path):                       # [in, d]: row-parallel
        return (_fit(mesh, r.tp, shape[0]), _fit(mesh, r.fsdp, shape[1]))
    if _REP_OUT.search(path):                   # [d, small]: fsdp rows only
        return (_fit(mesh, r.fsdp, shape[0]), None)
    if path.endswith(("/b",)):                  # column biases [out]
        return (_fit(mesh, r.tp, shape[0]),)
    if path.endswith("w_lora_a"):
        return (_fit(mesh, r.fsdp, shape[0]), None)
    if path.endswith("w_lora_b"):
        return (None, _fit(mesh, r.fsdp, shape[1]))
    if path.endswith("conv_x/w"):               # [K, d_in]
        return (None, _fit(mesh, r.tp, shape[1]))
    if path.endswith(("dt_bias", "a_log", "d_skip")):
        return (_fit(mesh, r.tp, shape[0]),)
    if path.endswith("mamba/norm/scale"):         # mamba inner norm [d_in]
        return (_fit(mesh, r.tp, shape[0]),)
    return (None,) * nd                          # replicate smalls


_ATTN_PROJ = re.compile(r"attn/(wq|wk|wv)/(w|b)$")


def _head_aligned(sub: str, spec, mesh: Mesh, r: Rules,
                  cfg: Optional[ArchConfig]):
    """Drop tp from attention K/V projections that would split a head.

    Megatron-style TP must shard q/k/v on the HEAD boundary: a tp axis that
    does not divide the head count would slice inside a single head's
    ``head_dim`` columns, which breaks RoPE's half-dim pairing (and, on some
    XLA versions, miscompiles under the layer scan). The GQA-standard
    fallback — K/V columns replicate while Q still shards — applies when
    ``n_heads`` divides the tp axis but ``n_kv_heads`` does not
    (tp > n_kv_heads with grouped queries), exactly how ``kv_cache_spec``
    already guards the cached heads.

    When even the QUERY heads cannot shard (``n_heads % tp != 0``), the old
    behavior silently replicated ALL q/k/v columns — attention ran with no
    tensor parallelism at all, and the only symptom was a quietly flat
    memory-per-device curve. That mesh/head mismatch is now a hard error;
    a head-group resharding rule for it stays a ROADMAP item.
    """
    if cfg is None:
        return spec
    m = _ATTN_PROJ.search(sub)
    if not m:
        return spec
    tp_size = max(_axsize(mesh, r.tp), 1)
    if cfg.n_heads % tp_size != 0:
        raise ValueError(
            f"attention TP mesh/head mismatch for {sub!r}: tp axes "
            f"{tuple(_flat_axes(r.tp))} (size {tp_size}) do not divide "
            f"n_heads={cfg.n_heads} (n_kv_heads={cfg.n_kv_heads}) — every "
            f"q/k/v column would silently replicate, disabling attention "
            f"tensor parallelism. Shrink the tp axis to a divisor of "
            f"n_heads, or wait for the head-group resharding rule "
            f"(ROADMAP: attention TP for tp > head count).")
    heads = cfg.n_heads if m.group(1) == "wq" else cfg.n_kv_heads
    if heads % tp_size == 0:
        return spec
    tp_axes = set(_flat_axes(r.tp))

    def strip(axes):
        if axes is None:
            return None
        kept = tuple(a for a in _flat_axes(axes) if a not in tp_axes)
        return kept[0] if len(kept) == 1 else (kept or None)

    # only the output-column dim (last) carries tp for these projections
    return tuple(spec[:-1]) + (strip(spec[-1]),)


def param_pspecs(params: PyTree, mesh: Mesh, rules: Optional[Rules] = None,
                 cfg: Optional[ArchConfig] = None) -> PyTree:
    """PartitionSpec tree mirroring ``params`` (works on ShapeDtypeStructs).

    ``cfg``, when provided, enables head-aligned attention TP (see
    :func:`_head_aligned`); without it the raw divisibility guards apply.
    """
    r = rules or Rules.for_mesh(mesh)

    def assign(path_tuple, leaf):
        path = "/".join(_key_str(k) for k in path_tuple)
        n = _n_stack(path)
        # strip the stack prefix components from the rule path
        sub = "/".join(path.split("/")[n:]) if n else path
        base = _base_spec(sub, leaf.shape[n:], mesh, r)
        base = _head_aligned(sub, tuple(base), mesh, r, cfg)
        return P(*((None,) * n + tuple(base)))

    return jax.tree_util.tree_map_with_path(assign, params)


def _key_str(k) -> str:
    if isinstance(k, jax.tree_util.DictKey):
        return str(k.key)
    if isinstance(k, jax.tree_util.SequenceKey):
        return str(k.idx)
    return str(getattr(k, "name", k))


# ---------------------------------------------------------------------------
# activation / batch / cache specs
# ---------------------------------------------------------------------------

def batch_axes(mesh: Mesh, rules: Rules, global_batch: int):
    """Largest subset of dp axes whose product divides global_batch."""
    return _fit(mesh, rules.dp, global_batch)


def batch_specs(cfg: ArchConfig, mesh: Mesh, rules: Rules, *, global_batch: int,
                with_positions: bool = True) -> dict:
    """Input shardings for a train/prefill batch dict."""
    ba = batch_axes(mesh, rules, global_batch)
    specs = {"labels": P(ba, None)}
    if cfg.input_mode == "tokens":
        specs["inputs"] = P(ba, None)
    else:
        specs["inputs"] = P(ba, None, None)
    if cfg.pos_embed == "mrope" and with_positions:
        specs["positions"] = P(ba, None, None)
    return specs


def _flat_axes(axes) -> tuple[str, ...]:
    if axes is None:
        return ()
    if isinstance(axes, str):
        return (axes,)
    return tuple(axes)


def kv_cache_spec(cfg: ArchConfig, mesh: Mesh, rules: Rules, *,
                  batch: int, n_stack: int = 1) -> P:
    """Spec for a stacked KV cache [stack.., B, S, Hkv, hd].

    Heads shard on tp when divisible; otherwise the sequence dim takes tp.
    Batch takes dp when divisible; otherwise sequence also absorbs dp.
    """
    ba = batch_axes(mesh, rules, batch)
    tp_on_heads = _fit(mesh, rules.tp, cfg.n_kv_heads)
    seq_axes: list[str] = []
    if ba is None:
        seq_axes.extend(_flat_axes(rules.dp))
    if tp_on_heads is None:
        seq_axes.extend(a for a in _flat_axes(rules.tp)
                        if a not in seq_axes)
    else:
        seq_axes.extend(a for a in _flat_axes(rules.tp)
                        if a not in _flat_axes(tp_on_heads)
                        and a not in seq_axes)
    seq = tuple(seq_axes) if seq_axes else None
    lead = (None,) * n_stack
    return P(*lead, ba, seq, tp_on_heads, None)


@dataclasses.dataclass(frozen=True)
class KVShardPlan:
    """Validated page-aligned sharding geometry for the paged KV pool.

    The pool's word axis is its sequence/page axis: word ``w`` belongs to
    page ``w // page_tokens`` and shard ``w // words_per_shard``. The plan
    guarantees every shard boundary is a page boundary, so a page (and
    therefore every word of a token's KV) lives on exactly one device and
    the host-side page tables stay replicated control plane.
    """
    n_shards: int
    n_pages: int
    page_tokens: int

    def __post_init__(self):
        if self.n_shards < 1:
            raise ValueError(f"n_shards must be >= 1, got {self.n_shards}")
        if self.n_pages % self.n_shards:
            raise ValueError(
                f"kv sharding is page-aligned: {self.n_pages} pages do not "
                f"divide across {self.n_shards} shards — round the pool up "
                f"to a whole number of pages per shard")

    @property
    def pages_per_shard(self) -> int:
        return self.n_pages // self.n_shards

    @property
    def words_per_shard(self) -> int:
        return self.pages_per_shard * self.page_tokens

    @property
    def num_words(self) -> int:
        return self.n_pages * self.page_tokens

    def shard_of_page(self, page: int) -> int:
        return page // self.pages_per_shard

    def shard_of_word(self, word: int) -> int:
        return word // self.words_per_shard


def kv_shard_plan(n_shards: int, *, n_pages: int,
                  page_tokens: int) -> KVShardPlan:
    """Page-aligned shard plan, rounding the pool UP to a whole number of
    pages per shard (extra capacity is harmless; a straddling page is not)."""
    if n_shards < 1:
        raise ValueError(f"n_shards must be >= 1, got {n_shards}")
    pages = -(-n_pages // n_shards) * n_shards
    return KVShardPlan(n_shards=n_shards, n_pages=pages,
                       page_tokens=page_tokens)


def shard_of_pages(plan: KVShardPlan, pages) -> int:
    """The ONE shard a page set lives on, raising when it spans several.

    Refcounted prefix sharing leans on this: shared pages pin to the shard
    where they were first written, a prefix chain therefore never crosses
    shards (each extension is carved from the attacher's home — the chain's
    shard — by construction), and an attaching sequence validates its
    adopted pages here before its home follows them. A multi-shard set is a
    bookkeeping corruption, not a capacity condition, hence ValueError
    rather than PoolCapacityError."""
    pages = list(pages)
    if not pages:
        raise ValueError("empty page set has no shard")
    shards = {plan.shard_of_page(int(p)) for p in pages}
    if len(shards) != 1:
        raise ValueError(
            f"page set {sorted(int(p) for p in pages)} spans shards "
            f"{sorted(shards)} — shared prefix pages must stay device-local")
    return shards.pop()


def kv_pool_spec(mesh: Mesh, *, num_words: int, page_tokens: int,
                 axis: str = "kv") -> P:
    """Spec for the paged pool storage ``[num_words, word_width]``: the word
    (= sequence/page) axis shards across ``axis`` with page-aligned
    boundaries. Raises when a shard boundary would straddle a page."""
    if axis not in mesh.axis_names:
        raise ValueError(f"mesh {mesh.axis_names} has no {axis!r} axis")
    n = int(mesh.shape[axis])
    if num_words % n:
        raise ValueError(
            f"pool of {num_words} words does not divide across the "
            f"{n}-way {axis!r} axis")
    if (num_words // n) % page_tokens:
        raise ValueError(
            f"shard boundary straddles a page: {num_words // n} words per "
            f"shard is not a multiple of page_tokens={page_tokens}")
    return P(axis, None)


def decode_state_pspecs(cfg: ArchConfig, mesh: Mesh, rules: Optional[Rules],
                        state: PyTree, *, batch: int) -> PyTree:
    """Spec tree for a decode state pytree (matches init_decode_state)."""
    r = rules or Rules.for_mesh(mesh)
    ba = batch_axes(mesh, r, batch)

    def assign(path_tuple, leaf):
        path = "/".join(_key_str(k) for k in path_tuple)
        nd = leaf.ndim
        if path == "len":
            return P(ba)
        if path in ("cache_k", "cache_v"):
            return kv_cache_spec(cfg, mesh, r, batch=batch, n_stack=1)
        if path in ("attn_k", "attn_v"):
            return kv_cache_spec(cfg, mesh, r, batch=batch, n_stack=1)
        if path.startswith(("tm_shift", "cm_shift")):    # [L, B, d]
            return P(None, ba, _fit(mesh, r.tp, leaf.shape[-1]))
        if path.startswith("tm_state"):                  # [L, B, H, K, V]
            return P(None, ba, _fit(mesh, r.tp, leaf.shape[2]), None, None)
        if path.startswith("conv/") or path.startswith("tail_conv/"):
            # [..., B, K-1, C]
            lead = nd - 3
            return P(*(None,) * lead, ba, None,
                     _fit(mesh, r.tp, leaf.shape[-1]))
        if path in ("ssm", "tail_ssm"):                  # [..., B, H, N, Phd]
            lead = nd - 4
            return P(*(None,) * lead, ba,
                     _fit(mesh, r.tp, leaf.shape[lead + 1]), None, None)
        return P(*(None,) * nd)

    return jax.tree_util.tree_map_with_path(assign, state)


def named(mesh: Mesh, spec_tree: PyTree) -> PyTree:
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, P))
