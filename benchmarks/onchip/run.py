"""Run one cell of the on-chip benchmark and print its result line.

From the root of a checkout, in one process that owns the chip(s):

    python3 benchmarks/onchip/run.py --workload resident-qskew2k \
        --seed 7 --seconds 10 --trace 0

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer metrics), ``device``, with ``--trace 1``
``breakdown``, and last ``checks``: each number the correctness check
compared, beside its limit.  Without a TPU, or without the program's
source beside this directory, it exits non-zero and prints no such line.
"""
import time

T_START = time.perf_counter()

import sys  # noqa: E402

if __name__ == "__main__":
    import harness

    sys.exit(harness.main(sys.argv[1:], T_START))
