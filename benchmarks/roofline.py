"""§Roofline: three-term model per (arch x shape x mesh) from dry-run
artifacts (artifacts/dryrun/*.json — written by repro.launch.dryrun).

Terms (seconds per step, PER CHIP; HLO numbers are already per-device):
  compute    = dot_flops / peak bf16 FLOP/s
  memory     = traffic_bytes / peak HBM bytes/s
  collective = wire_bytes / one ICI link's bytes/s (conservative;
               ring multipliers: all-reduce 2x, others 1x)
with the peaks of the chip the records model, looked up in ``PEAKS`` by
its ``device_kind`` (the dry-run models TPU v5e meshes).

Also reports MODEL_FLOPS (6*N_active*D train, 2*N_active*D inference),
the useful-compute ratio MODEL_FLOPS / (dot_flops * chips), the dominant
term, and a what-would-move-it hint.
"""
from __future__ import annotations

import glob
import json
import os

from repro.configs import registry

# Published per-chip peaks keyed by jax's ``device_kind``. TPU v5e: Google
# Cloud documentation, "TPU v5e" — 197 TFLOP/s bf16, 819 GB/s HBM, 1,600
# Gbit/s of interconnect over 4 ICI links (50 GB/s per link).
PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bw": 819e9, "link_bw": 50e9},
}
DRYRUN_DEVICE_KIND = "TPU v5 lite"


def peaks(device_kind: str) -> dict:
    """The peak table row for a device kind; an unknown kind is an error,
    never a default."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; known: {sorted(PEAKS)}") from None

_RING_MULT = {"all-reduce": 2.0, "all-gather": 1.0, "reduce-scatter": 1.0,
              "all-to-all": 1.0, "collective-permute": 1.0}


def wire_bytes(coll: dict) -> float:
    return sum(_RING_MULT[k] * v for k, v in coll.items())


def model_flops(arch: str, kind: str, tokens: int) -> float:
    cfg = registry.get(arch)
    n = cfg.active_param_count()
    return (6.0 if kind == "train" else 2.0) * n * tokens


def analyze_record(rec: dict) -> dict:
    pk = peaks(DRYRUN_DEVICE_KIND)
    h = rec["hlo"]
    chips = rec["chips"]
    compute = h["dot_flops"] / pk["flops"]
    memory = h["traffic_bytes"] / pk["hbm_bw"]
    coll = wire_bytes(h["collective_bytes"]) / pk["link_bw"]
    terms = {"compute": compute, "memory": memory, "collective": coll}
    dominant = max(terms, key=terms.get)
    mf = model_flops(rec["arch"], rec["kind"], rec["tokens_per_step"])
    useful = mf / max(h["dot_flops"] * chips, 1.0)
    step_time = max(terms.values())
    mfu = (mf / chips / pk["flops"]) / max(step_time, 1e-30)
    hints = {
        "compute": "raise MFU: cut non-model dot flops (remat policy, "
                   "attention chunking) or use a faster layout",
        "memory": "cut HBM traffic: bf16 intermediates, fuse elementwise "
                  "chains, larger per-step tiles, avoid scan-carry copies",
        "collective": "reshard: fewer all-gathers (FSDP prefetch), 2D-shard "
                      "logits, hierarchical/int8 cross-pod reduce",
    }
    return {
        "arch": rec["arch"], "shape": rec["shape"], "mesh": rec["mesh"],
        "kind": rec["kind"], "chips": chips,
        "compute_s": compute, "memory_s": memory, "collective_s": coll,
        "dominant": dominant,
        "model_flops": mf, "hlo_flops_global": h["dot_flops"] * chips,
        "useful_ratio": useful, "roofline_mfu": mfu,
        "memory_gib": rec["memory"]["temp_bytes"] / 2**30,
        "args_gib": rec["memory"]["argument_bytes"] / 2**30,
        "hint": hints[dominant],
    }


def load_all(art_dir: str = "artifacts/dryrun", variant: str = "") -> list[dict]:
    rows = []
    for path in sorted(glob.glob(os.path.join(art_dir, "*.json"))):
        base = os.path.basename(path)[:-5]
        is_variant = base.count("__") > 2
        if (variant and variant not in base) or (not variant and is_variant):
            continue
        with open(path) as f:
            rec = json.load(f)
        if rec.get("status") != "ok":
            rows.append({"arch": rec["arch"], "shape": rec["shape"],
                         "mesh": rec["mesh"], "error": rec.get("error")})
            continue
        rows.append(analyze_record(rec))
    return rows


def main() -> None:
    rows = load_all()
    print(f"# roofline terms per cell (seconds/step/chip; "
          f"{DRYRUN_DEVICE_KIND} peaks)")
    print("arch,shape,mesh,chips,compute_s,memory_s,collective_s,dominant,"
          "useful_ratio,roofline_mfu")
    for r in rows:
        if "error" in r:
            print(f"{r['arch']},{r['shape']},{r['mesh']},ERROR")
            continue
        print(f"{r['arch']},{r['shape']},{r['mesh']},{r['chips']},"
              f"{r['compute_s']:.3e},{r['memory_s']:.3e},"
              f"{r['collective_s']:.3e},{r['dominant']},"
              f"{r['useful_ratio']:.3f},{r['roofline_mfu']:.4f}")


if __name__ == "__main__":
    main()
