"""Everything a run reads is found by name from ``BENCHMARK.json``.

A cell names a configuration and a traffic mix. The configuration's file
is the ``file`` its entry in ``configs`` gives; its architecture's module
(program check, matmul count, plain reference) ``arch/<model_type>.py``,
from the file's ``model_type``; the traffic mix is
``traffic/<mix>.json``; its generator ``generators/<generator>.py``; each
metric's reader ``metrics/<metric>.py``; each cell's correctness limit
``checks/<cell>.json``. Adding any of them is adding a file, and a cell
is one more entry in ``workloads``.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
import sys

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]


@dataclasses.dataclass(frozen=True)
class Widths:
    """A configuration's sizes, as the work counts and the reference read
    them."""
    layers: int
    hidden: int
    heads: int
    kv_heads: int
    head_dim: int
    vocab: int
    dtype_bytes: int
    rope_theta: float
    norm_eps: float
    matmul_params: int         # per token, from the architecture's module
    tied: bool = False         # output head tied to the embedding
    config: tuple = ()         # the file's top-level settings, (key, value)

    @classmethod
    def of(cls, conf: dict, arch) -> "Widths":
        return cls(layers=conf["num_hidden_layers"],
                   hidden=conf["hidden_size"],
                   heads=conf["num_attention_heads"],
                   kv_heads=conf["num_key_value_heads"],
                   head_dim=conf["head_dim"],
                   vocab=conf["vocab_size"],
                   dtype_bytes={"bfloat16": 2, "float32": 4}[
                       conf["torch_dtype"]],
                   rope_theta=float(conf["rope_theta"]),
                   norm_eps=float(conf["rms_norm_eps"]),
                   matmul_params=int(arch.matmul_params(conf)),
                   tied=bool(conf.get("tie_word_embeddings", False)),
                   config=tuple(sorted(
                       (k, v) for k, v in conf.items()
                       if isinstance(v, (bool, int, float, str)))))


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict               # the configuration file's contents
    arch: object               # its module, arch/<model_type>.py
    traffic: dict              # the traffic file's contents
    check: dict                # the cell's correctness limit
    metrics: list              # [(entry in BENCHMARK.json, reader module)]

    @property
    def widths(self) -> Widths:
        return Widths.of(self.config, self.arch)


def load_benchmark(root: pathlib.Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def load_module(path: pathlib.Path):
    """Import a file by path (metric readers and generators carry dots and
    dashes in their names, so they are not importable as modules)."""
    if not path.is_file():
        raise FileNotFoundError(f"no such file: {path}")
    stem = path.stem.replace(".", "_").replace("-", "_")
    name = f"benchmarks.chip.{path.parent.name}_{stem}"
    if getattr(sys.modules.get(name), "__file__", None) == str(path):
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def arch(model_type: str):
    """The module of the architecture ``model_type`` (``arch/qwen2.py``
    for ``qwen2``); FileNotFoundError, naming the path, where there is
    none."""
    return load_module(HERE / "arch" / f"{model_type}.py")


def reports(entry: dict, cell: str) -> bool:
    """Whether a metric entry is reported in ``cell``."""
    return "workloads" not in entry or cell in entry["workloads"]


def load_cell(name: str, *, traced: bool, root: pathlib.Path = ROOT,
              bench: dict | None = None) -> Cell:
    """The cell called ``name`` with its files; ``traced`` picks the
    per-layer metrics, else the end-to-end ones."""
    bench = load_benchmark(root) if bench is None else bench
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    confs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((root / confs[w["config"]]["file"]).read_text())
    traffic = json.loads((HERE / "traffic" / f"{w['traffic']}.json")
                         .read_text())
    check = json.loads((HERE / "checks" / f"{name}.json").read_text())
    kind = "per_layer" if traced else "end_to_end"
    metrics = [(m, load_module(HERE / "metrics" / f"{m['name']}.py"))
               for m in bench[kind] if reports(m, name)]
    return Cell(name=name, chips=int(w["chips"]), config=config,
                arch=arch(config["model_type"]), traffic=traffic,
                check=check, metrics=metrics)


def generator(traffic: dict):
    return load_module(HERE / "generators" / f"{traffic['generator']}.py")
