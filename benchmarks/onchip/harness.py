"""One run of one benchmark cell, found by name.

``BENCHMARK.json`` names each cell's configuration and traffic.  The
configuration is its ``file`` (``configs/<config>.json``) and, beside it,
``configs/<config>.py``, which builds the program for the cell and holds
its reference (see ``configs/fedar-mlp-resident.py`` for what it
provides).  The traffic is ``traffic/<traffic>.json``, the limits of the
check ``limits/<cell>.json``, and each per-layer metric is read by
``metrics/<metric>.py``.  Nothing here names a cell, a configuration or an
engine, so a new cell, configuration or metric is new files and entries
only.

A run:

1. draws the fleet and the initial weights from the seed (``fleetgen``)
   and has the configuration build the program from them;
2. drives three rounds through the program's round call -- they compile
   the round -- and keeps what each produced for the check;
3. drives the same call back to back for ``seconds`` (a closed loop: the
   next round starts when the last one's host sync returns), counting the
   real sample-epochs of each round and the compilations in the window,
   under the profiler when ``trace`` is on;
4. reads the device's peak memory, frees the program, runs the
   configuration's reference over the same three rounds and compares.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import re
import shutil
import sys
import tempfile
import time
import types
from pathlib import Path

import numpy as np

import counting
import fleetgen
import tracereduce

HERE = Path(__file__).resolve().parent
CHECKED_ROUNDS = 3
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileCounter:
    """XLA compilations (persistent-cache loads included) since creation."""

    def __init__(self):
        import jax

        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, _secs, **_):
        if event == COMPILE_EVENT:
            self.count += 1


# -- finding a cell by name ------------------------------------------------
def load_cell(root: Path, workload: str, here: Path = HERE) -> dict:
    """The cell ``workload`` of ``root/BENCHMARK.json``, with its
    configuration, traffic, limits and metric lists."""
    bench = json.loads((Path(root) / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json "
                         f"(known: {sorted(cells)})")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config_file = Path(root) / configs[cell["config"]]["file"]
    config = json.loads(config_file.read_text())
    traffic = json.loads((here / "traffic" / f"{cell['traffic']}.json")
                         .read_text())
    limits = json.loads((here / "limits" / f"{workload}.json").read_text())

    def mine(metrics):
        return [m for m in metrics
                if workload in m.get("workloads", [workload])]

    return {
        "name": workload,
        "chips": int(cell["chips"]),
        "spec": cell_spec(config, traffic),
        "system": load_module(config_file.with_suffix(".py"),
                              "onchip_config_"),
        "traffic": traffic,
        "limits": limits,
        "end_to_end": mine(bench["end_to_end"]),
        "per_layer": mine(bench["per_layer"]),
        "here": here,
    }


def cell_spec(config: dict, traffic: dict) -> dict:
    """What the program and the reference are both built from: the
    configuration, with the traffic's ``fed`` settings over its own and the
    traffic's data layout."""
    return {**config, "fed": {**config.get("fed", {}),
                              **traffic.get("fed", {})},
            "layout": traffic.get("layout", "auto")}


def load_module(path: Path, prefix: str):
    """The Python file ``path``, loaded as a module of its own."""
    name = prefix + re.sub(r"\W", "_", Path(path).stem)
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_reader(here: Path, metric: str):
    """``metrics/<metric>.py``'s ``read``."""
    return load_module(Path(here) / "metrics" / f"{metric}.py",
                       "onchip_metric_").read


# -- the device ------------------------------------------------------------
def check_device(chips: int) -> dict:
    """The device record; anything but ``chips`` or more TPUs is refused."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"needs a TPU, JAX found {devices[0].platform!r}")
    if len(devices) < chips:
        raise SystemExit(f"the cell needs {chips} TPU devices, JAX found "
                         f"{len(devices)}")
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def memory_peak_bytes():
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


# -- the window ------------------------------------------------------------
def window(system, seconds, sizes, epochs, counter):
    """Back-to-back ``system.round()`` calls for ``seconds``; returns the
    rounds, their real sample-epochs and selected clients, the elapsed
    seconds from the window's start to the last round's end, each round's
    end, and the compilations in between."""
    from jax.profiler import TraceAnnotation

    before = counter.count
    rounds = work = clients = 0
    ends = []
    # set-up's objects leave the collector's scans, so a full collection in
    # the window costs what the window allocated, not the whole heap
    gc.collect()
    gc.freeze()
    t0 = time.perf_counter()
    while True:
        with TraceAnnotation("bench.round"):
            selected = system.round()
        with TraceAnnotation("bench.count"):
            work += counting.sample_epochs(selected, sizes, epochs)
            clients += int(np.count_nonzero(selected))
            rounds += 1
        elapsed = time.perf_counter() - t0
        ends.append(elapsed)
        if elapsed >= seconds:
            break
    return {"rounds": rounds, "sample_epochs": work, "clients": clients,
            "elapsed_s": elapsed, "ends": ends,
            "compiles": counter.count - before, "start": t0}


def round_times(ends) -> dict:
    """How the window's round times spread, for the log: a run that reads
    far off shows here whether a few rounds stalled or all ran slower."""
    ms = 1000.0 * np.diff(np.concatenate([[0.0], ends]))
    med = float(np.median(ms))
    slow = ms > 3.0 * med
    return {"p50_ms": med, "p99_ms": float(np.percentile(ms, 99)),
            "max_ms": float(ms.max()), "slow_rounds": int(slow.sum()),
            "slow_excess_s": float((ms[slow] - med).sum() / 1000.0)}


# -- the run ---------------------------------------------------------------
def run_cell(cell: dict, seed: int, seconds: float, trace: bool,
             device: dict, t_start: float, counter: CompileCounter,
             log=lambda s: print(s, file=sys.stderr, flush=True)) -> dict:
    """One run; returns the contract line as a dict.  A per-layer reader
    gets the window's rounds, rate, sample-epochs and selected clients,
    the model, the chips, their peaks and the reduced trace."""
    import jax

    spec, config = cell["spec"], cell["system"]
    marks = {"start": time.perf_counter() - t_start}
    fleet = fleetgen.make_fleet(cell["traffic"], seed)
    weights = fleetgen.init_weights(seed, spec["model"])
    weights0 = {k: np.asarray(v) for k, v in weights.items()}
    marks["fleet"] = time.perf_counter() - t_start
    system = config.build(spec, fleet, weights)
    del weights
    marks["server"] = time.perf_counter() - t_start
    prog = system.checked(CHECKED_ROUNDS)
    marks["checked_rounds"] = time.perf_counter() - t_start
    log(json.dumps({**system.describe(), "setup_marks_s": marks}))
    epochs = spec["fed"]["local_epochs"]
    trace_dir = Path(tempfile.mkdtemp(prefix="onchip_trace_")) if trace \
        else None
    if trace:
        jax.profiler.start_trace(str(trace_dir))
    win = window(system, seconds, fleet.sizes, epochs, counter)
    if trace:
        jax.profiler.stop_trace()
    setup_s = win["start"] - t_start
    peak = memory_peak_bytes()
    log(json.dumps({"memory_peak_bytes": peak, "rounds": win["rounds"],
                    "window_s": win["elapsed_s"],
                    "round_times": round_times(win["ends"])}))
    system.close()
    del system
    gc.collect()

    t_ref = time.perf_counter()
    ref = config.reference(fleet, spec, weights0, prog)
    numbers = config.compare(prog, ref, weights0)
    log(json.dumps({"reference_s": time.perf_counter() - t_ref}))
    numbers["window_compiles"] = win["compiles"]
    limits = cell["limits"]
    failed = failed_checks(numbers, limits)
    samples_per_s = win["sample_epochs"] / win["elapsed_s"]
    device = {**device, "memory_peak_bytes": peak}
    line = {"correct": not failed, "attempted": win["rounds"],
            "failed": len(failed)}
    if not trace:
        values = {"samples_per_s": samples_per_s, "setup_s": setup_s}
        line["metrics"] = {
            m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in cell["end_to_end"]}
        line["device"] = device
    else:
        reduced = tracereduce.reduce(tracereduce.load_planes(trace_dir),
                                     tracereduce.load_layers())
        shutil.rmtree(trace_dir, ignore_errors=True)
        readings = types.SimpleNamespace(
            rounds=win["rounds"], samples_per_s=samples_per_s,
            sample_epochs=win["sample_epochs"], clients=win["clients"],
            model=spec["model"], chips=cell["chips"],
            peaks=counting.chip_peaks(device["kind"]), trace=reduced)
        metrics = {}
        for m in cell["per_layer"]:
            value = load_reader(cell["here"], m["name"])(readings)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        line["metrics"] = metrics
        line["device"] = {**device, "busy_s": reduced.mean_busy_s,
                          "window_s": reduced.window_s}
        line["breakdown"] = {"device_ops": reduced.top_ops,
                             "idle_gaps": reduced.idle_by_host}
    line["checks"] = {k: {"value": numbers[k], "limit": limits[k]}
                      for k in limits}
    return line


def failed_checks(numbers: dict, limits: dict) -> list:
    """The numbers that are not finite or exceed their limit."""
    return [k for k in limits
            if not (np.isfinite(numbers[k]) and numbers[k] <= limits[k])]


def main(argv, t_start: float) -> int:
    import argparse

    ap = argparse.ArgumentParser(description="Run one on-chip benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        raise SystemExit("--seed must be non-negative")
    root = Path.cwd()
    src = root / "src"
    if not (src / "repro").is_dir():
        raise SystemExit(f"no program source under {src}")
    sys.path.insert(0, str(src))
    cell = load_cell(root, args.workload)
    device = check_device(cell["chips"])

    import repro
    from repro.common.compile_cache import enable_compile_cache

    if not Path(repro.__file__).resolve().is_relative_to(src.resolve()):
        raise SystemExit(f"the program imported from {repro.__file__}, "
                         f"not from {src}")
    enable_compile_cache()
    counter = CompileCounter()
    line = run_cell(cell, args.seed, args.seconds, bool(args.trace), device,
                    t_start, counter)
    for name, c in line["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0
