"""Readings the correctness limits are set from, on the chip, in one process.

    python3 benchmarks/onchip/calibrate.py --workload resident-qskew2k \
        --seeds 11,12,13 --controls 3

For each seed: the program's first three rounds at the cell's own size,
compared with the float32 reference (the lower readings); and for the
first ``--controls`` seeds each of ``--kinds``, compared the same way (the
upper readings): the controls ``fp8`` (the reference with float8 matmul
operands put in the program's place) and ``bfloat16`` (the reference held
in bfloat16), and the planted faults.  One JSON line per seed and reading
on standard output.  The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

import harness

FAULTS = ("half_clients", "client_flipped")
PRECISIONS = ("fp8", "bfloat16")


def readings(cell, seed, kinds):
    spec, config = cell["spec"], cell["system"]
    fleet, weights, weights0 = harness.draw(cell, seed)
    system = config.build(spec, fleet, weights)
    prog = system.checked(harness.CHECKED_ROUNDS)
    system.close()
    del system, weights
    gc.collect()
    ref = config.reference(fleet, spec, weights0, prog)
    out = [("program", config.compare(prog, ref, weights0))]
    for kind in kinds:
        other = config.reference(
            fleet, spec, weights0, prog,
            precision=kind if kind in PRECISIONS else "float32",
            fault=kind if kind in FAULTS else None)
        out.append((kind, config.compare(other, ref, weights0)))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--kinds", default=",".join(PRECISIONS + FAULTS),
                    help="readings taken on the first --controls seeds")
    args = ap.parse_args(argv)
    root = Path.cwd()
    sys.path.insert(0, str(root / "src"))
    cell = harness.load_cell(root, args.workload)
    device = harness.check_device(cell["chips"])
    from repro.common.compile_cache import enable_compile_cache

    enable_compile_cache()
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        kinds = args.kinds.split(",") if i < args.controls else []
        for kind, numbers in readings(cell, seed, kinds):
            print(json.dumps({"workload": args.workload, "seed": seed,
                              "kind": kind, "device": device["kind"],
                              **numbers}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
