"""Run one cell traced, as ``run.py --trace 1`` does, and add the per-layer
metrics that read the program's own phase scopes, host spans and counters.

From the root of a checkout, in one process that owns the chip(s):

    python3 benchmarks/onchip/spanrun.py --workload resident-qskew2k \
        --seed 7 --seconds 10

The harness reduces a trace with ``tracereduce`` alone and gives its metric
readers no spans, so the metrics of ``scopes.json`` (read by
``metrics/<name>.py`` from ``spanreduce``) are not in ``run.py``'s line.
This runs the harness unchanged and always traced, keeps the planes it
loads, reduces them with ``spanreduce`` too, and prints the harness's line
with those metrics added to ``metrics`` and the reduction, per round,
under ``spans``.

A v5e trace names each op by its HLO text, without the ``op_name`` that
holds its phase, so XLA dumps the compiled modules' HLO text (the
persistent compilation cache is off in this process: a program loaded from
it is never dumped) and ``spanreduce`` maps ops to phases through it.
"""
import contextlib
import io
import json
import os
import shutil
import sys
import tempfile
import time
import types
from pathlib import Path

T_START = time.perf_counter()

import harness  # noqa: E402
import spanreduce  # noqa: E402
import tracereduce  # noqa: E402


def main(argv) -> int:
    # before the harness first touches JAX
    hlo_dir = tempfile.mkdtemp(prefix="onchip_hlo_")
    os.environ["XLA_FLAGS"] = " ".join(filter(None, [
        os.environ.get("XLA_FLAGS"), f"--xla_dump_to={hlo_dir}",
        "--xla_dump_hlo_as_text"]))
    os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
    kept = []
    load = tracereduce.load_planes

    def load_and_keep(trace_dir):
        planes = list(load(trace_dir))
        kept.append(planes)
        return planes

    tracereduce.load_planes = load_and_keep
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = harness.main(list(argv) + ["--trace", "1"], T_START)
    line = json.loads(out.getvalue().strip().splitlines()[-1])
    scopes = spanreduce.load_scopes()
    dumps = Path(hlo_dir).glob("*after_optimizations.txt")
    op_names = spanreduce.hlo_op_names(f.read_text() for f in dumps)
    shutil.rmtree(hlo_dir, ignore_errors=True)
    spans = spanreduce.reduce(kept[-1], scopes, op_names=op_names)
    readings = types.SimpleNamespace(rounds=line["attempted"], spans=spans)
    for m in scopes["metrics"]:
        value = harness.load_reader(harness.HERE, m["name"])(readings)
        if value is not None:
            line["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}
    line["spans"] = spans.per_round(line["attempted"])
    print(json.dumps(line), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
