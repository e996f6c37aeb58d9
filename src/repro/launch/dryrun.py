import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: prove every (arch x input-shape x mesh) combination
lowers AND compiles against the production mesh, and extract the roofline
terms from the compiled artifact.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch tinyllama-1.1b \
      --shape train_4k [--multi-pod] [--out out.json]
  PYTHONPATH=src python -m repro.launch.dryrun --all --out dryrun_all.jsonl

The first two lines of this file MUST stay ahead of any other import: jax
locks the device count on first init, and the production mesh needs 512
placeholder host devices.  (No ``from __future__ import annotations`` here
for the same reason — the XLA_FLAGS lines must be the very first statements.)
"""
import argparse
import json
import re
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.common.config import INPUT_SHAPES, TrainConfig
from repro.configs import ARCH_IDS, cfg_for_shape, get_config
from repro.launch import sharding
from repro.launch.input_specs import abstract_params, input_specs
from repro.launch.mesh import TARGET_KIND, chip_peaks, make_production_mesh
from repro.launch.train import TrainState, build_train_step
from repro.models.model import Model
from repro.optim.optimizers import make_optimizer

TARGET = chip_peaks(TARGET_KIND)  # the v5e chip these rooflines model

COLLECTIVE_RE = re.compile(
    r"=\s*(\w[\w\d\[\],{}\s]*?)\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
)
SHAPE_RE = re.compile(r"(f32|bf16|f16|s32|u32|s8|u8|pred|f64|s64)\[([\d,]*)\]")

DTYPE_BYTES = {
    "f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4,
    "s8": 1, "u8": 1, "pred": 1, "f64": 8, "s64": 8,
}


def collective_bytes(hlo_text: str) -> dict:
    """Per-device bytes moved by collectives, parsed from the partitioned
    HLO.  Keyed by op kind; result-shape bytes (per-partition shapes)."""
    out = {
        "all-gather": 0, "all-reduce": 0, "reduce-scatter": 0,
        "all-to-all": 0, "collective-permute": 0,
    }
    for line in hlo_text.splitlines():
        line = line.strip()
        m = COLLECTIVE_RE.search(line)
        if not m or "-start" in line.split("=")[0]:
            pass
        kind = None
        for k in out:
            if re.search(rf"\b{k}(-start)?\(", line):
                kind = k
                break
        if kind is None:
            continue
        # result shape(s) appear right after '='
        eq = line.find("=")
        if eq < 0:
            continue
        rhs = line[eq + 1 :]
        paren = rhs.find("(")
        head = rhs[: paren if paren > 0 else len(rhs)]
        nbytes = 0
        for dt, dims in SHAPE_RE.findall(head):
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            nbytes += n * DTYPE_BYTES[dt]
        out[kind] += nbytes
    return out


def build_abstract_state(model: Model, tc: TrainConfig):
    params = abstract_params(model.cfg)
    opt = make_optimizer(tc)
    opt_state = jax.eval_shape(opt.init, params)
    step = jax.ShapeDtypeStruct((), jnp.int32)
    return TrainState(params, opt_state, step)


def lower_one(arch, shape_name, *, multi_pod=False, tc=None,
              extra_tags=None):
    """Lower + compile one (arch, shape, mesh) and return the record."""
    from jax.sharding import PartitionSpec as P

    shape = INPUT_SHAPES[shape_name]
    cfg = cfg_for_shape(get_config(arch), shape)
    model = Model(cfg)
    mesh = make_production_mesh(multi_pod=multi_pod)
    tc = tc or TrainConfig(optimizer="sgd", lr=1e-2, remat=True,
                           loss_chunk=512 if cfg.vocab_size > 100_000 else 0)

    t0 = time.time()
    with mesh:
        if shape.kind == "train":
            step_fn = build_train_step(model, tc)
            state = build_abstract_state(model, tc)
            batch = input_specs(cfg, shape)
            pspecs = sharding.param_specs(state.params, mesh)
            state_specs = TrainState(
                params=pspecs,
                opt_state=sharding.param_specs(state.opt_state, mesh)
                if jax.tree.leaves(state.opt_state)
                else state.opt_state,
                step=P(),
            )
            bspecs = sharding.batch_specs(batch, mesh)
            lowered = jax.jit(
                step_fn,
                in_shardings=sharding.named(mesh, (state_specs, bspecs)),
            ).lower(state, batch)
        elif shape.kind == "prefill":
            batch = input_specs(cfg, shape)
            params = abstract_params(cfg)
            pspecs = sharding.param_specs(params, mesh)
            bspecs = sharding.batch_specs(batch, mesh)

            def prefill(params, batch):
                return model.prefill(params, batch, remat=False)

            lowered = jax.jit(
                prefill, in_shardings=sharding.named(mesh, (pspecs, bspecs))
            ).lower(params, batch)
        else:  # decode
            inp = input_specs(cfg, shape)
            params = abstract_params(cfg)
            pspecs = sharding.param_specs(params, mesh)
            cspecs = sharding.cache_specs(inp["cache"], mesh)
            tspec = sharding.batch_specs({"tokens": inp["tokens"]}, mesh)["tokens"]

            def serve_step(params, cache, tokens, pos):
                return model.decode_step(params, cache, tokens, pos)

            lowered = jax.jit(
                serve_step,
                in_shardings=sharding.named(mesh, (pspecs, cspecs, tspec, P())),
            ).lower(params, inp["cache"], inp["tokens"], inp["pos"])

        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    cost = compiled.cost_analysis() or {}
    if isinstance(cost, list):  # older jax: one dict per device
        cost = cost[0] if cost else {}
    try:
        mem = compiled.memory_analysis()
        mem_rec = {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "generated_code_bytes": getattr(mem, "generated_code_size_in_bytes", None),
        }
    except Exception as e:  # CPU backend may not implement it
        mem_rec = {"error": str(e)}

    coll = collective_bytes(compiled.as_text())

    chips = int(np.prod(mesh.devices.shape))
    flops = float(cost.get("flops", 0.0))
    bytes_accessed = float(cost.get("bytes accessed", 0.0))
    coll_total = float(sum(coll.values()))

    record = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "x".join(map(str, mesh.devices.shape)),
        "multi_pod": multi_pod,
        "chips": chips,
        "kind": shape.kind,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "hlo_flops": flops,
        "hlo_bytes": bytes_accessed,
        "collective_bytes": coll,
        "collective_bytes_total": coll_total,
        # roofline terms (seconds). cost_analysis flops/bytes are per-device
        # post-partitioning on the CPU backend; see benchmarks/roofline.py.
        "t_compute": flops / TARGET.flops_bf16,
        "t_memory": bytes_accessed / TARGET.hbm_bw,
        "t_collective": coll_total / TARGET.ici_bw,
        "memory": mem_rec,
    }
    if extra_tags:
        record.update(extra_tags)
    return record


def pattern_period(cfg) -> int:
    """Smallest repeating block-pattern unit (layers)."""
    if cfg.shared_attn_every:
        return cfg.shared_attn_every
    if cfg.global_every:
        return cfg.global_every
    if "s" in cfg.block_pattern:
        return 2  # xlstm (sLSTM, mLSTM) pair
    return 1


def roofline_one(arch, shape_name, *, multi_pod=False, tc=None,
                 policy="fsdp_tp", cfg_over=None):
    """Scan-corrected roofline terms.

    XLA cost_analysis counts a scan body ONCE regardless of trip count, so
    full-depth scanned records under-report flops/bytes by ~L.  Here we
    compile UNROLLED width-identical variants at n1 = period and
    n2 = 2*period layers and extrapolate linearly:
        X_L = X_n1 + ((L - n1) / period) * (X_n2 - X_n1).
    """
    import dataclasses

    shape = INPUT_SHAPES[shape_name]
    base_cfg = cfg_for_shape(get_config(arch), shape)
    if cfg_over:
        base_cfg = dataclasses.replace(base_cfg, **cfg_over)
    L = base_cfg.num_layers
    period = pattern_period(base_cfg)
    n1, n2 = period, 2 * period

    tc = tc or TrainConfig(
        optimizer="sgd", lr=1e-2, remat=False, unroll=True,
        loss_chunk=512 if base_cfg.vocab_size > 100_000 else 0,
    )

    r1 = _lower_cfg(dataclasses.replace(base_cfg, num_layers=n1),
                    arch, shape_name, multi_pod=multi_pod, tc=tc, policy=policy)
    r2 = _lower_cfg(dataclasses.replace(base_cfg, num_layers=n2),
                    arch, shape_name, multi_pod=multi_pod, tc=tc, policy=policy)
    scale = (L - n1) / period

    def extra(key):
        return r1[key] + scale * (r2[key] - r1[key])

    coll = {
        k: r1["collective_bytes"][k]
        + scale * (r2["collective_bytes"][k] - r1["collective_bytes"][k])
        for k in r1["collective_bytes"]
    }
    rec = dict(r1)
    rec.update(
        hlo_flops=extra("hlo_flops"),
        hlo_bytes=extra("hlo_bytes"),
        collective_bytes=coll,
        collective_bytes_total=float(sum(coll.values())),
        roofline_mode="unroll_extrapolated",
        period=period,
        n1=n1,
        n2=n2,
        compile_s=r1["compile_s"] + r2["compile_s"],
    )
    rec["t_compute"] = rec["hlo_flops"] / TARGET.flops_bf16
    rec["t_memory"] = rec["hlo_bytes"] / TARGET.hbm_bw
    rec["t_collective"] = rec["collective_bytes_total"] / TARGET.ici_bw
    return rec


def _lower_cfg(cfg, arch, shape_name, *, multi_pod, tc, policy="fsdp_tp"):
    """lower_one for an explicit (possibly depth-truncated) config."""
    from jax.sharding import PartitionSpec as P

    shape = INPUT_SHAPES[shape_name]
    model = Model(cfg)
    mesh = make_production_mesh(multi_pod=multi_pod)

    t0 = time.time()
    if shape.kind == "train":
        step_fn = build_train_step(model, tc)
        state = build_abstract_state(model, tc)
        batch = input_specs(cfg, shape)
        state_specs = TrainState(
            params=sharding.param_specs(state.params, mesh, policy=policy),
            opt_state=sharding.param_specs(state.opt_state, mesh)
            if jax.tree.leaves(state.opt_state) else state.opt_state,
            step=P(),
        )
        bspecs = sharding.batch_specs(batch, mesh)
        lowered = jax.jit(
            step_fn,
            in_shardings=sharding.named(mesh, (state_specs, bspecs)),
        ).lower(state, batch)
    elif shape.kind == "prefill":
        batch = input_specs(cfg, shape)
        params = abstract_params(cfg)
        pspecs = sharding.param_specs(params, mesh, policy=policy)
        bspecs = sharding.batch_specs(batch, mesh)
        lowered = jax.jit(
            lambda p, b: model.prefill(p, b, remat=False, unroll=tc.unroll),
            in_shardings=sharding.named(mesh, (pspecs, bspecs)),
        ).lower(params, batch)
    else:
        inp = input_specs(cfg, shape)
        params = abstract_params(cfg)
        pspecs = sharding.param_specs(params, mesh, policy=policy)
        cspecs = sharding.cache_specs(inp["cache"], mesh)
        tspec = sharding.batch_specs({"tokens": inp["tokens"]}, mesh)["tokens"]
        lowered = jax.jit(
            lambda p, c, t, pos: model.decode_step(p, c, t, pos, unroll=tc.unroll),
            in_shardings=sharding.named(mesh, (pspecs, cspecs, tspec, P())),
        ).lower(params, inp["cache"], inp["tokens"], inp["pos"])
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    cost = compiled.cost_analysis() or {}
    if isinstance(cost, list):  # older jax: one dict per device
        cost = cost[0] if cost else {}
    coll = collective_bytes(compiled.as_text())
    return {
        "arch": arch,
        "shape": shape_name,
        "mesh": "x".join(map(str, mesh.devices.shape)),
        "multi_pod": multi_pod,
        "chips": int(np.prod(mesh.devices.shape)),
        "kind": shape.kind,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "hlo_flops": float(cost.get("flops", 0.0)),
        "hlo_bytes": float(cost.get("bytes accessed", 0.0)),
        "collective_bytes": coll,
        "collective_bytes_total": float(sum(coll.values())),
        "policy": policy,
        "memory": {},
    }


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, choices=ARCH_IDS + [None])
    ap.add_argument("--shape", default=None, choices=list(INPUT_SHAPES) + [None])
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--roofline", action="store_true",
                    help="unroll-extrapolated cost records (see roofline_one)")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    archs = ARCH_IDS if (args.all or not args.arch) else [args.arch]
    shapes = list(INPUT_SHAPES) if (args.all or not args.shape) else [args.shape]
    # roofline table is single-pod only (the multi-pod pass proves sharding)
    if args.roofline and not args.both_meshes:
        meshes = [args.multi_pod]
    else:
        meshes = [False, True] if (args.both_meshes or args.all) else [args.multi_pod]

    sink = open(args.out, "a") if args.out else None
    ok = True
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                try:
                    if args.roofline:
                        rec = roofline_one(arch, shape, multi_pod=mp)
                    else:
                        rec = lower_one(arch, shape, multi_pod=mp)
                    status = "OK"
                except Exception as e:
                    rec = {
                        "arch": arch, "shape": shape, "multi_pod": mp,
                        "error": f"{type(e).__name__}: {e}"[:500],
                    }
                    status = "FAIL"
                    ok = False
                line = json.dumps(rec)
                if sink:
                    sink.write(line + "\n")
                    sink.flush()
                tail = (f" compile={rec.get('compile_s')}s" if status == "OK"
                        else f" {rec.get('error', '')[:200]}")
                print(f"[{status}] {arch} x {shape} multi_pod={mp}" + tail)
    if sink:
        sink.close()
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    main()
