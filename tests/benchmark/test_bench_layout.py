"""Everything the chip benchmark reads is found by name from
BENCHMARK.json, and the file keeps to the benchmark's contract."""
import json
import pathlib
import re
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks.chip import discover  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_top_level_keys_and_sizes():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    assert (ROOT / BENCH["command"][1]).is_file()
    for p in BENCH["paths"]:
        assert (ROOT / p).is_dir() and not p.startswith("/") and ".." not in p
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


def test_names_units_and_text_fields():
    names = ([c["name"] for c in BENCH["configs"]] + CELLS
             + [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]])
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for entry in BENCH["configs"] + BENCH["workloads"]:
        assert 1 <= len(entry["why"]) <= 200 and "\n" not in entry["why"]


def test_every_cell_reports_setup_another_end_to_end_and_a_layer():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    for cell in CELLS:
        got = [m["name"] for m in BENCH["end_to_end"]
               if discover.reports(m, cell)]
        assert "setup_s" in got and len(got) >= 2, cell
        layers = [m for m in BENCH["per_layer"] if discover.reports(m, cell)]
        assert layers, cell
        for m in layers:
            assert discover.reports(e2e[m["moves"]], cell), (cell, m["name"])


def test_shares_of_a_roofline_or_peak_are_percent():
    for m in BENCH["per_layer"]:
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%", m


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("traced", [False, True])
def test_cell_files_found_by_name(cell, traced):
    c = discover.load_cell(cell, traced=traced)
    assert c.chips == 1
    assert c.metrics, cell
    for entry, mod in c.metrics:
        assert callable(mod.read), entry["name"]
    assert discover.generator(c.traffic).generate
    assert c.check["limits"] and all(v > 0 for v in c.check["limits"].values())
    assert c.arch.__file__ == str(discover.HERE / "arch"
                                  / f"{c.config['model_type']}.py")
    w = c.widths
    assert w.heads % w.kv_heads == 0 and w.matmul_params > 0
    eng = c.config["engine"]
    t = c.traffic
    assert t["prompt_tokens"]["max"] + t["output_tokens"]["max"] \
        <= eng["max_len"]
    assert eng["max_slots"] * eng["max_len"] <= 4096


def test_config_files_list_their_cuts():
    """No width is cut; the vocabulary may be sliced (one chip's share)."""
    for c in BENCH["configs"]:
        conf = json.loads((ROOT / c["file"]).read_text())
        assert sorted(c["reduced"]) == sorted(conf["reduced"])
        for k in c["reduced"]:
            assert k == "vocab_size" or not k.endswith(
                ("_dim", "_rank", "_size")), k
