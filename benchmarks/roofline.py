"""Roofline analysis over the dry-run records (EXPERIMENTS.md §Roofline).

Reads dryrun_all.jsonl (written by repro.launch.dryrun), attaches analytic
MODEL_FLOPS = 6·N(active)·D (train) / 2·N·D (prefill) / 2·N (decode, per
token) and emits the three roofline terms + dominant bottleneck per
(arch x shape x mesh).

Methodology notes:
  * cost_analysis() flops/bytes on the CPU backend are per-partition (the
    post-SPMD module is the per-device program), so terms are per-chip.
  * collective bytes are summed result-shape bytes of partitioned collective
    ops (per-device wire-bytes proxy); ICI term assumes 1 link direction.
"""
from __future__ import annotations

import json
import os
from typing import Optional

import jax
import numpy as np

from repro.common.config import INPUT_SHAPES
from repro.configs import get_config
from repro.launch.mesh import TARGET_KIND, chip_peaks

TARGET = chip_peaks(TARGET_KIND)  # the v5e chip these rooflines model

DRYRUN_PATH = os.environ.get("DRYRUN_PATH", "dryrun_all.jsonl")
ROOFLINE_PATH = os.environ.get("ROOFLINE_PATH", "roofline_all.jsonl")


def param_counts(arch: str):
    """(total, active) param counts from the abstract init tree."""
    from repro.launch.input_specs import abstract_params

    cfg = get_config(arch)
    tree = abstract_params(cfg)
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    total = active = 0
    E = max(cfg.num_experts, 1)
    k = cfg.num_experts_per_tok or 0
    for path, leaf in flat:
        keys = "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)
        n = int(np.prod(leaf.shape))
        total += n
        if cfg.num_experts and "moe" in keys and any(
            w in keys for w in ("w_gate", "w_up", "w_down")
        ):
            active += n * k // E  # only top-k experts touched per token
        else:
            active += n
    return total, active


def model_flops(arch: str, shape_name: str) -> float:
    """Global analytic useful FLOPs for one step of the workload."""
    shape = INPUT_SHAPES[shape_name]
    total, active = param_counts(arch)
    B, S = shape.global_batch, shape.seq_len
    if shape.kind == "train":
        return 6.0 * active * B * S
    if shape.kind == "prefill":
        return 2.0 * active * B * S
    return 2.0 * active * B  # decode: one token per sequence


def load_records(path: Optional[str] = None):
    """Prefer scan-corrected (unroll-extrapolated) records; fall back to the
    raw full-depth compile records."""
    path = path or (ROOFLINE_PATH if os.path.exists(ROOFLINE_PATH) else DRYRUN_PATH)
    recs = []
    if not os.path.exists(path):
        return recs
    with open(path) as f:
        for line in f:
            r = json.loads(line)
            if "error" not in r:
                recs.append(r)
    # de-dup (arch, shape, multi_pod) keeping the latest
    seen = {}
    for r in recs:
        seen[(r["arch"], r["shape"], r["multi_pod"])] = r
    return list(seen.values())


def analyse(rec: dict) -> dict:
    chips = rec["chips"]
    mf = model_flops(rec["arch"], rec["shape"])
    t_c = rec["hlo_flops"] / TARGET.flops_bf16
    t_m = rec["hlo_bytes"] / TARGET.hbm_bw
    t_x = rec["collective_bytes_total"] / TARGET.ici_bw
    dom = max(("compute", t_c), ("memory", t_m), ("collective", t_x),
              key=lambda kv: kv[1])[0]
    useful = mf / chips / max(rec["hlo_flops"], 1.0)
    return {
        **rec,
        "model_flops_global": mf,
        "t_compute": t_c,
        "t_memory": t_m,
        "t_collective": t_x,
        "dominant": dom,
        "useful_flop_ratio": useful,
    }


def rows(single_pod_only: bool = True):
    out = []
    for r in load_records():
        if single_pod_only and r["multi_pod"]:
            continue
        a = analyse(r)
        out.append((
            f"roofline_{a['arch']}_{a['shape']}",
            0.0,
            f"dom={a['dominant']};tc={a['t_compute']:.2e};"
            f"tm={a['t_memory']:.2e};tx={a['t_collective']:.2e};"
            f"useful={a['useful_flop_ratio']:.3f}",
        ))
    return out


def full_table():
    recs = [analyse(r) for r in load_records()]
    recs.sort(key=lambda r: (r["arch"], r["shape"], r["multi_pod"]))
    return recs


if __name__ == "__main__":
    for r in full_table():
        print(
            f"{r['arch']:18s} {r['shape']:12s} mesh={r['mesh']:8s} "
            f"dom={r['dominant']:10s} tc={r['t_compute']:.3e} "
            f"tm={r['t_memory']:.3e} tx={r['t_collective']:.3e} "
            f"useful={r['useful_flop_ratio']:.3f}"
        )
