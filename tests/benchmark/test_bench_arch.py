"""Architecture-specific parts of the chip benchmark live in one module per
architecture, ``arch/<model_type>.py``, found from the configuration
file's ``model_type``.

A configuration whose layers differ from Qwen2's joins by new files alone:
a copy of ``benchmarks/chip`` gains ``arch/llama.py`` (no QKV bias), a
configuration of the registry's tinyllama-1.1b at its reduced preset, a
traffic mix, a check and entries in its ``BENCHMARK.json``; no file of the
copy is edited. On the CPU, with the chip check skipped, the run serves
it, compares it with its own reference, and judges a fault not correct.
The qwen2 module's numbers are pinned to what the harness read before it
had modules."""
import json
import pathlib
import shutil
import sys

import jax
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks.chip import discover, run  # noqa: E402
from test_bench_run import _break_step, off_chip  # noqa: E402

DATA = pathlib.Path(__file__).resolve().parent / "data"
CELL = "tinyllama-1.1b.tiny-poisson"
# file in the copy -> fixture under data/
NEW = {"arch/llama.py": "arch_llama.py",
       "configs/tinyllama-1.1b.json": "tinyllama-1.1b.json",
       "traffic/tiny-poisson.json": "tiny-poisson.json",
       f"checks/{CELL}.json": f"{CELL}.json"}
CONFIG = {"name": "tinyllama-1.1b",
          "source": "https://huggingface.co/TinyLlama/"
                    "TinyLlama-1.1B-intermediate-step-1431k-3T",
          "file": "benchmarks/chip/configs/tinyllama-1.1b.json",
          "reduced": [], "why": "pre-norm GQA without bias, on the CPU"}
WORKLOAD = {"name": CELL, "config": "tinyllama-1.1b",
            "traffic": "tiny-poisson", "chips": 1,
            "why": "short Poisson chats on 4 slots, on the CPU"}
QWEN_FILE = ROOT / "benchmarks" / "chip" / "configs" / "qwen2.5-3b.json"


def _copy(tmp_path, monkeypatch, model_type=None):
    """A copy of the benchmark with only the new files added (and the
    configuration's ``model_type`` set, where given); ``discover`` reads
    the copy. Returns the copy's root."""
    here = tmp_path / "benchmarks" / "chip"
    shutil.copytree(discover.HERE, here,
                    ignore=shutil.ignore_patterns("__pycache__"))
    for dst, src in NEW.items():
        assert not (here / dst).exists(), dst
        shutil.copy(DATA / src, here / dst)
    if model_type is not None:
        conf = here / "configs" / "tinyllama-1.1b.json"
        conf.write_text(json.dumps(dict(json.loads(conf.read_text()),
                                        model_type=model_type)))
    bench = discover.load_benchmark()
    bench["configs"].append(CONFIG)
    bench["workloads"].append(WORKLOAD)
    for m in bench["end_to_end"]:
        m.get("workloads", []).append(CELL)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    monkeypatch.setattr(discover, "HERE", here)
    return tmp_path


def test_new_architecture_files_are_found(tmp_path, monkeypatch):
    root = _copy(tmp_path, monkeypatch)
    here = root / "benchmarks" / "chip"
    cell = discover.load_cell(CELL, traced=False, root=root)
    assert cell.arch.__file__ == str(here / "arch" / "llama.py")
    assert cell.config == json.loads((DATA / "tinyllama-1.1b.json")
                                     .read_text())
    assert cell.traffic == json.loads((DATA / "tiny-poisson.json")
                                      .read_text())
    assert cell.check == json.loads((DATA / f"{CELL}.json").read_text())
    assert [m["name"] for m, _ in cell.metrics] == ["setup_s",
                                                    "output_tokens_per_s"]
    assert all(mod.__file__.startswith(str(here / "metrics"))
               for _, mod in cell.metrics)
    # no QKV bias: the count is the qwen2 formula's, over these widths
    assert cell.widths.matmul_params == 2 * (
        64 * 64 + 2 * 64 * 16 + 64 * 64 + 3 * 64 * 128)
    assert dict(cell.widths.config)["model_type"] == "llama"


@pytest.mark.parametrize("fault", [None, "token"])
def test_new_architecture_is_served_and_judged(tmp_path, monkeypatch,
                                               fault):
    """A sound run is correct against the module's own reference; a token
    altered where it is produced is not."""
    root = _copy(tmp_path, monkeypatch)
    off_chip(monkeypatch)
    if fault:
        _break_step(monkeypatch, fault)
    cell = discover.load_cell(CELL, traced=False, root=root)
    out = run.measure(cell, seed=2**33 + 7, seconds=4.0, trace=False,
                      devs=jax.devices()[:1])
    got = {k: v["value"] for k, v in out["check"].items()}
    assert out["correct"] is (fault is None), got
    assert got["window_compiles"] == 0 and got["short_answers"] == 0
    assert run.judge(got, cell.check["limits"]) is (fault is None)


def test_same_file_as_qwen2_stops_at_the_program_check(tmp_path,
                                                      monkeypatch, capsys):
    root = _copy(tmp_path, monkeypatch, model_type="qwen2")
    off_chip(monkeypatch)
    cell = discover.load_cell(CELL, traced=False, root=root)
    with pytest.raises(SystemExit) as e:
        run.program_config(cell)
    assert e.value.code == 2
    err = capsys.readouterr().err
    assert "qkv_bias" in err and "(False, True)" in err


def test_model_type_without_module_fails_at_load(tmp_path, monkeypatch):
    root = _copy(tmp_path, monkeypatch, model_type="no_such_model")
    want = str(root / "benchmarks" / "chip" / "arch" / "no_such_model.py")
    with pytest.raises(FileNotFoundError, match=want):
        discover.load_cell(CELL, traced=False, root=root)


def test_qwen2_matmul_params_pinned():
    """qwen2.5-3b's per-token matmul parameters, as ``mfu`` counted them
    before the count moved into the module (3 x hidden x ffn a layer)."""
    qwen2 = discover.arch("qwen2")
    conf = json.loads(QWEN_FILE.read_text())
    assert qwen2.matmul_params(conf) == 2_774_532_096
    assert discover.Widths.of(conf, qwen2).matmul_params == 2_774_532_096


def test_qwen2_program_pinned():
    """The registry attributes the run checked at qwen2.5-3b before the
    check moved into the module, to the value."""
    conf = json.loads(QWEN_FILE.read_text())
    assert discover.arch("qwen2").program(conf) == {
        "n_layers": 36, "d_model": 2048, "n_heads": 16, "n_kv_heads": 2,
        "head_dim_": 128, "d_ff": 11008, "vocab": 151936,
        "rope_theta": 1e6, "norm_eps": 1e-6, "qkv_bias": True}


def test_qwen2_program_refuses_a_head_size_off_hidden():
    """Every Qwen2 has hidden == heads x head_dim; a file that breaks it is
    no Qwen2."""
    conf = dict(json.loads(QWEN_FILE.read_text()), head_dim=64)
    with pytest.raises(ValueError, match="num_attention_heads"):
        discover.arch("qwen2").program(conf)
