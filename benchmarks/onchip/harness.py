"""One run of one benchmark cell, found by name.

``BENCHMARK.json`` names each cell's configuration and traffic.  The
configuration is its ``file`` (``configs/<config>.json``) and, beside it,
``configs/<config>.py``, which draws the cell's fleet and initial weights,
gives the work counts its readers take, builds the program and holds its
reference (``configs/fedar-mlp-resident.py`` lists what it provides).  Of
a fleet the harness reads ``sizes`` (each client's samples: rows, token
sequences, whatever the configuration's unit of local SGD is) and
``num_clients``; of the weights, that they are a pytree of arrays.  The
traffic is ``traffic/<traffic>.json``, the limits of the check
``limits/<cell>.json``, and each per-layer metric is read by
``metrics/<metric>.py``.  Device ops map to layers by the name patterns of
``layers.json``, and the program's phase scopes are ``scopes.json``'s; a
configuration's ``.json`` may add ``layers`` and ``phases`` of its own,
which count for its cells alone.  Nothing here names a cell, a
configuration, a model or an engine, so a new cell, configuration or
metric is new files and entries only.

A run:

1. has the configuration draw the fleet and the initial weights from the
   seed, and build the program from them;
2. drives three rounds through the program's round call -- they compile
   the round -- and keeps what each produced for the check;
3. drives the same call back to back for ``seconds`` (a closed loop: the
   next round starts when the last one's host sync returns), counting the
   real sample-epochs of each round and the compilations in the window,
   under the profiler when ``trace`` is on;
4. reads the device's peak memory, frees the program, runs the
   configuration's reference over the same three rounds and compares;
5. traced, reduces the trace to layers (``tracereduce``) and to the
   program's phases, spans and counters (``spanreduce``), and has each
   per-layer metric's reader read them.

A traced run (``--trace 1``) has XLA dump every compiled module's HLO
text, with JAX's persistent compilation cache off: a v5e trace names an op
by its HLO text without the ``op_name`` that holds its phase, so
``spanreduce`` finds it in the dump, and a program loaded from the cache
is never dumped.  An untraced run does neither.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import os
import re
import shutil
import sys
import tempfile
import time
import types
from pathlib import Path

import numpy as np

import counting
import spanreduce
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
    configuration, traffic, limits, metric lists, layers and phases."""
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
    layers, scopes = with_own_layers_and_phases(
        cell["config"], config, tracereduce.load_layers(),
        spanreduce.load_scopes())

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
        "layers": layers,
        "scopes": scopes,
        "here": here,
    }


def with_own_layers_and_phases(name: str, config: dict, layers: dict,
                               scopes: dict):
    """``layers.json`` and ``scopes.json`` with the configuration's own
    ``layers`` (layer -> op-name patterns) and ``phases`` added after
    theirs.  A layer or phase they already name is an error: a
    configuration adds names, it never redefines one."""
    own_layers = config.get("layers", {})
    own_phases = config.get("phases", [])
    clash = sorted(
        (set(own_layers) & {*layers["layers"], layers["rest"]})
        | (set(own_phases) & set(scopes["phases"])))
    if clash:
        raise ValueError(f"configuration {name!r} names layers or phases "
                         f"the benchmark already has: {clash}")
    return ({**layers, "layers": {**layers["layers"], **own_layers}},
            {**scopes, "phases": [*scopes["phases"], *own_phases]})


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
def draw(cell: dict, seed: int):
    """The fleet and the initial weights, as the cell's configuration draws
    them from ``seed``, and a host copy of the weights for the reference
    (the program may take over the device's)."""
    import jax

    config = cell["system"]
    fleet = config.make_fleet(cell["traffic"], seed, cell["spec"])
    weights = config.init_weights(seed, cell["spec"])
    return fleet, weights, jax.tree_util.tree_map(np.asarray, weights)


def run_cell(cell: dict, seed: int, seconds: float, trace: bool,
             device: dict, t_start: float, counter: CompileCounter,
             log=lambda s: print(s, file=sys.stderr, flush=True),
             hlo_dir: Path | None = None) -> dict:
    """One run; returns the contract line as a dict.  Traced, the per-layer
    metrics are read from ``readings``, with the op names of the modules
    XLA dumped to ``hlo_dir``."""
    import jax

    spec, config = cell["spec"], cell["system"]
    marks = {"start": time.perf_counter() - t_start}
    fleet, weights, weights0 = draw(cell, seed)
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
                    "window_s": win["elapsed_s"], "setup_s": setup_s,
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
    device = {**device, "memory_peak_bytes": peak}
    line = {"correct": not failed, "attempted": win["rounds"],
            "failed": len(failed)}
    if not trace:
        values = {"samples_per_s": win["sample_epochs"] / win["elapsed_s"],
                  "setup_s": setup_s}
        line["metrics"] = {
            m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in cell["end_to_end"]}
        line["device"] = device
    else:
        r = readings(cell, win, device, tracereduce.load_planes(trace_dir),
                     compiled_op_names(hlo_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        log(json.dumps({"spans_per_round": r.spans.per_round(r.rounds)}))
        line["metrics"] = per_layer_metrics(cell, r)
        line["device"] = {**device, "busy_s": r.trace.mean_busy_s,
                          "window_s": r.trace.window_s}
        line["breakdown"] = {"device_ops": r.trace.top_ops,
                             "idle_gaps": r.trace.idle_by_host}
    line["checks"] = {k: {"value": numbers[k], "limit": limits[k]}
                      for k in limits}
    return line


def readings(cell: dict, win: dict, device: dict, planes,
             op_names: dict | None = None) -> types.SimpleNamespace:
    """What a per-layer reader reads of a traced run: the window's
    ``rounds``, real ``sample_epochs`` and selected ``clients``, the
    configuration's ``counts``, the ``chips`` and their ``peaks``, the
    trace reduced to the cell's layers (``trace``, ``tracereduce``) and to
    the program's phases, spans and counters (``spans``, ``spanreduce``;
    ``op_names`` map a v5e trace's ops to their phases)."""
    planes = list(planes)
    return types.SimpleNamespace(
        rounds=win["rounds"], sample_epochs=win["sample_epochs"],
        clients=win["clients"], counts=cell["system"].counts(cell["spec"]),
        chips=cell["chips"], peaks=counting.chip_peaks(device["kind"]),
        trace=tracereduce.reduce(planes, cell["layers"]),
        spans=spanreduce.reduce(planes, cell["scopes"], op_names=op_names))


def per_layer_metrics(cell: dict, r) -> dict:
    """Each of the cell's per-layer metrics whose reader finds something
    in ``r``; one that finds nothing (``None``) is left out."""
    metrics = {}
    for m in cell["per_layer"]:
        value = load_reader(cell["here"], m["name"])(r)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    return metrics


def dump_compiled_hlo() -> Path:
    """Have XLA write every module it compiles, as optimized HLO text, to a
    new directory, and turn JAX's persistent compilation cache off (a
    program loaded from it is never dumped).  Before JAX is imported: both
    are read as it starts."""
    if "jax" in sys.modules:
        raise RuntimeError("the HLO dump is set up before JAX is imported")
    hlo_dir = Path(tempfile.mkdtemp(prefix="onchip_hlo_"))
    os.environ["XLA_FLAGS"] = " ".join(filter(None, [
        os.environ.get("XLA_FLAGS"), f"--xla_dump_to={hlo_dir}",
        "--xla_dump_hlo_as_text"]))
    os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
    return hlo_dir


def compiled_op_names(hlo_dir: Path | None) -> dict:
    """``spanreduce.hlo_op_names`` of the optimized modules XLA dumped to
    ``hlo_dir``; none without a dump."""
    if hlo_dir is None:
        return {}
    dumps = sorted(Path(hlo_dir).glob("*after_optimizations.txt"))
    return spanreduce.hlo_op_names(f.read_text() for f in dumps)


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
    hlo_dir = dump_compiled_hlo() if args.trace else None
    try:
        sys.path.insert(0, str(src))
        cell = load_cell(root, args.workload)
        device = check_device(cell["chips"])

        import repro
        from repro.common.compile_cache import enable_compile_cache

        if not Path(repro.__file__).resolve().is_relative_to(src.resolve()):
            raise SystemExit(f"the program imported from {repro.__file__}, "
                             f"not from {src}")
        if not args.trace:
            enable_compile_cache()
        counter = CompileCounter()
        line = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                        device, t_start, counter, hlo_dir=hlo_dir)
    finally:
        if hlo_dir is not None:
            shutil.rmtree(hlo_dir, ignore_errors=True)
    for name, c in line["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0
