"""Drive the FedAR engine's main path once on a TPU and check what it returns.

Run from the repository root, in one process that owns the chip:

    python3 chip_smoke.py              # one chip: phases P1, P2, P3
    python3 chip_smoke.py --chips 4    # four chips: mesh phases M2, M3 only

Every phase goes through the user entry points (``FedARServer`` ->
``FedAREngine`` / ``CohortEngine``) with the paper's client model at full
width (``MnistConfig()``, 784-128-10) and the ``*_impl`` knobs on ``auto``,
so on a TPU local SGD, aggregation, the defense and the uplink codec
(qsgd's pack/unpack, top-k's selection) run as compiled Pallas kernels.  All data comes from the repository's seeded
synthetic sources.

  P1  the paper's 12 robots (Table II fleet, 300 samples each, B=20, E=5,
      dense FoolsGold, 10 rounds), run twice in this process: kernels
      (``auto``) and the XLA reference (``einsum``, matmuls at ``highest``
      precision).  Round-1 params must agree to ``P1_PARAM_RTOL`` relative
      L2, final accuracy to ``P1_ACC_TOL``, and reach ``P1_ACC_FLOOR``.
  P2  the resident engine at 2,048 quantity-skewed clients (100 samples
      each, packed layout, ``select_frac=0.5``, top-k uplink), 3 rounds:
      the fused ragged local-SGD kernel, the top-k selection kernel and the
      uplink decoded by its kept mask.
  P3  the host-store cohort engine over a 1,000,000-client virtual fleet
      (K=512 per round, async aggregation, 4-bit qsgd, sketched FoolsGold,
      chaos faults), 3 rounds: ``fedavg_agg``, ``sketch_similarity`` and
      the 4-bit pack/unpack kernels.
  M2  (``--chips 4``) P2 on a 4-way ``clients`` mesh vs one device.
  M3  (``--chips 4``) P3 on a 4-way mesh with the tree reduce vs one device.

Each phase prints one JSON line (the route each hot op took, compile and
steady seconds per round timed to ``block_until_ready``, the compilations
the steady rounds triggered — a phase fails unless there are none —,
accuracy, the data-fallback flag, ``peak_bytes_in_use``).  The last line is
``{"ok": true, "device": {...}}``.  Without a TPU, or run outside the
repository, the script exits non-zero before printing it.
"""
from __future__ import annotations

import argparse
import json
import statistics
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

P1_PARAM_RTOL = 1e-2  # relative L2 of the round-1 global params
P1_ACC_TOL = 0.03
P1_ACC_FLOOR = 0.8
# mesh parity: the CPU parity suite's tolerances for compressed runs
# (tests/test_mesh_engine.py::test_sharded_compressed_matches_single_device)
MESH_TRUST_ATOL = 1e-4
MESH_PARAM_TOL = 1e-3

def check_device(devices, chips: int) -> dict:
    """The device record of the final line; anything but ``chips`` TPU
    devices is refused (there is no CPU path)."""
    platform = devices[0].platform
    if platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, JAX found {platform!r}")
    if len(devices) < chips:
        raise SystemExit(
            f"chip_smoke: --chips {chips} needs {chips} TPU devices, JAX "
            f"found {len(devices)}"
        )
    return {"platform": platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def _timed(fn):
    import jax

    t = time.perf_counter()
    out = fn()
    jax.block_until_ready(out)
    return out, time.perf_counter() - t


def _peak_bytes():
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def _flat(params):
    import numpy as np

    from repro.core.engine import flatten

    return np.asarray(flatten(params), np.float64)


def _eval_set(n=500):
    import jax.numpy as jnp

    from repro.data.sources import eval_source

    src, _ = eval_source("synthetic", False)
    x, y = src.sample(n, seed=99)
    return jnp.asarray(x), jnp.asarray(y)


def _kernel_count(lowered) -> int:
    """``tpu_custom_call`` sites (Pallas kernels) in a lowered program."""
    return lowered.as_text().count("tpu_custom_call")


def _server(num_clients, impl="auto", **fed_kw):
    from repro import FedARServer, TaskRequirement
    from repro.configs.fedar_mnist import MnistConfig, fleet_fed

    knobs = {f"{k}_impl": impl for k in ("sgd", "agg", "defense", "compress")}
    fed = fleet_fed(num_clients, local_epochs=5, local_batch_size=20,
                    timeout=10.0, **knobs, **fed_kw)
    return FedARServer(MnistConfig(), fed, TaskRequirement())


def _steady_round(server, data, eval_set, reps=3):
    """Median seconds of an already-compiled round, on a throwaway copy of
    the resident state (the run's own state is untouched), and the
    compilations those rounds triggered (a steady round must add none, or
    its time is a compile's)."""
    from repro.common.tracing import compile_count

    engine, state = server.engine, server.state
    before, times = compile_count(), []
    for _ in range(reps):
        _, dt = _timed(lambda: engine.step(state, data, eval_set=eval_set))
        times.append(dt)
    return statistics.median(times), compile_count() - before


def run_paper_fleet(impl: str, *, rounds: int = 10, samples: int = 300):
    """One P1 run: round 1 through ``run_round`` (the jitted step), the
    rest in one ``lax.scan`` through ``run``."""
    import numpy as np

    from repro import make_federated

    ds = make_federated("table2", 12, samples_per_client=samples)
    server = _server(12, impl, aggregation="fedar", defense="foolsgold")
    data = server.engine.prepare_data(ds)
    ev = _eval_set()
    _, first = _timed(lambda: server.run_round(data, eval_set=ev))
    params1 = _flat(server.params)
    kernels = _kernel_count(
        server.engine.lower_step(server.state, data, eval_set=ev)
    )
    _, scan_s = _timed(lambda: server.run(data, rounds - 1, eval_set=ev))
    steady, compiles = _steady_round(server, data, ev)
    final = _flat(server.params)
    return {
        "routes": server.engine.kernel_routes(),
        "kernels_in_program": kernels,
        "compile_s": first - steady,
        "steady_s_per_round": steady,
        "steady_compiles": compiles,
        "scan_s": scan_s,
        "acc": server.history["acc"][-1],
        "finite": bool(np.isfinite(final).all()),
        "fallback": bool(ds.fallback),
    }, params1


def phase_p1(*, impl="auto", rounds=10, samples=300) -> dict:
    import jax
    import numpy as np

    kern, p_k = run_paper_fleet(impl, rounds=rounds, samples=samples)
    with jax.default_matmul_precision("highest"):
        ref, p_x = run_paper_fleet("einsum", rounds=rounds, samples=samples)
    rel = float(np.linalg.norm(p_k - p_x) / np.linalg.norm(p_x))
    line = {
        "phase": "P1", "kernel": kern, "xla": ref,
        "round1_param_rel_l2": rel,
        "acc_diff": abs(kern["acc"] - ref["acc"]),
        "peak_bytes_in_use": _peak_bytes(),
    }
    line["ok"] = (
        kern["finite"] and ref["finite"] and rel <= P1_PARAM_RTOL
        and line["acc_diff"] <= P1_ACC_TOL and kern["acc"] >= P1_ACC_FLOOR
        and kern["steady_compiles"] == 0
    )
    return line


def _rounds_by_step(server, data, rounds, eval_set):
    """``rounds`` (>= 2) jitted rounds through ``run_round`` -> (first-round
    seconds, median seconds of the later rounds, compilations the later
    rounds triggered)."""
    from repro.common.tracing import compile_count

    times = []
    for r in range(rounds):
        if r == 1:
            before = compile_count()
        _, dt = _timed(lambda: server.run_round(data, eval_set=eval_set))
        times.append(dt)
    return times[0], statistics.median(times[1:]), compile_count() - before


def run_resident(*, clients=2048, samples=100, rounds=3, impl="auto",
                 mesh=None):
    """P2's config (and M2's, with ``mesh``): the packed, gated, top-k
    resident engine.  Returns the phase line and the server."""
    import jax
    import numpy as np

    from repro import make_federated

    ds = make_federated("digits", clients, scenario="quantity_skew",
                        samples_per_client=samples)
    dense_bytes = int(np.prod(ds.x.shape)) * 4
    server = _server(clients, impl, defense="foolsgold_sketch",
                     select_frac=0.5, compress="topk", mesh_shape=mesh)
    data = server.engine.prepare_data(ds)
    fallback = bool(ds.fallback)
    del ds  # the dense host rectangle never reaches the device
    ev = _eval_set()
    kernels = _kernel_count(
        server.engine.lower_step(server.state, data, eval_set=ev)
    )
    first, steady, compiles = _rounds_by_step(server, data, rounds, ev)
    final = _flat(server.params)
    line = {
        "routes": server.engine.kernel_routes(),
        "kernels_in_program": kernels,
        "layout": "packed" if "packed" in data else "dense",
        "device_data_bytes": sum(a.nbytes for a in jax.tree.leaves(data)),
        "dense_rect_bytes": dense_bytes,
        "compile_s": first - steady,
        "steady_s_per_round": steady,
        "steady_compiles": compiles,
        "acc": server.history["acc"][-1],
        "finite": bool(np.isfinite(final).all()),
        "fallback": fallback,
        "peak_bytes_in_use": _peak_bytes(),
    }
    line["ok"] = (
        line["finite"] and line["layout"] == "packed"
        and "x" not in data
        and line["device_data_bytes"] < dense_bytes
        and compiles == 0
    )
    return line, server


def phase_p2(**kw) -> dict:
    line, _ = run_resident(**kw)
    return {"phase": "P2", **line}


def run_cohort(*, clients=1_000_000, cohort=512, samples=300, rounds=3,
               impl="auto", mesh=None):
    """P3's config (and M3's, with ``mesh``): the host-store cohort engine
    with async aggregation, 4-bit qsgd, the sketched defense and chaos."""
    import numpy as np

    from repro.data.datasets import VirtualFleet

    fleet = VirtualFleet(clients, samples_per_client=samples)
    server = _server(clients, impl, aggregation="async",
                     defense="foolsgold_sketch", cohort_size=cohort,
                     compress="qsgd", compress_bits=4, faults="chaos",
                     mesh_shape=mesh)
    ev = _eval_set()
    first, steady, compiles = _rounds_by_step(server, fleet, rounds, ev)
    kernels = _kernel_count(server.engine.lower_round(fleet, eval_set=ev))
    final = _flat(server.params)
    line = {
        "routes": server.engine.kernel_routes(),
        "kernels_in_program": kernels,
        "compile_s": first - steady,
        "steady_s_per_round": steady,
        "steady_compiles": compiles,
        "acc": server.history["acc"][-1],
        "finite": bool(np.isfinite(final).all()),
        "fallback": False,  # the virtual fleet is synthetic by construction
        "peak_bytes_in_use": _peak_bytes(),
    }
    line["ok"] = line["finite"] and compiles == 0
    return line, server


def phase_p3(**kw) -> dict:
    line, _ = run_cohort(**kw)
    return {"phase": "P3", **line}


def _mesh_parity(name, run, **kw) -> dict:
    """Run a config on one device, then on the 4-way mesh, and compare
    them as the CPU parity suite does."""
    import numpy as np

    one, s1 = run(**kw)
    h1, p1, t1 = s1.history, _flat(s1.params), np.asarray(s1.history["trust"])
    del s1
    four, s4 = run(mesh=4, **kw)
    h4, p4, t4 = s4.history, _flat(s4.params), np.asarray(s4.history["trust"])
    same_sel = all(np.array_equal(a, b)
                   for a, b in zip(h1["selected"], h4["selected"]))
    trust_err = float(np.abs(t1 - t4).max())
    param_ok = bool(np.allclose(p1, p4, atol=MESH_PARAM_TOL,
                                rtol=MESH_PARAM_TOL))
    return {
        "phase": name, "one_device": one, "mesh4": four,
        "selected_equal": same_sel, "trust_max_abs_err": trust_err,
        "param_max_abs_err": float(np.abs(p1 - p4).max()),
        "ok": (one["ok"] and four["ok"] and same_sel
               and trust_err <= MESH_TRUST_ATOL and param_ok),
    }


def phase_m2(**kw) -> dict:
    return _mesh_parity("M2", run_resident, **kw)


def phase_m3(**kw) -> dict:
    return _mesh_parity("M3", run_cohort, **kw)


def _all_kernel(line) -> bool:
    """Every hot op took its Pallas kernel (the defense/codec may be off)."""
    parts = [line[k] for k in ("kernel", "one_device", "mesh4") if k in line]
    for part in parts or [line]:
        routes = part["routes"]
        if not routes["sgd"].startswith("fused"):
            return False
        if any(routes[k] not in ("kernel", "none")
               for k in ("agg", "defense")):
            return False
        if routes["compress"] not in ("kernel", "none"):
            return False
        if part["kernels_in_program"] < 1:
            return False
    return True


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4],
                    help="4 runs only the mesh phases (M2, M3) and their "
                         "one-device comparisons")
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        raise SystemExit(f"chip_smoke: no repository source under {ROOT}")
    sys.path.insert(0, str(ROOT / "src"))

    import jax

    from repro.common.compile_cache import enable_compile_cache

    device = check_device(jax.devices(), args.chips)
    cache = enable_compile_cache()
    print(json.dumps({"compile_cache": cache}), flush=True)
    phases = ([phase_m2, phase_m3] if args.chips == 4
              else [phase_p1, phase_p2, phase_p3])
    ok = True
    for phase in phases:
        t = time.perf_counter()
        line = phase()
        line["wall_s"] = time.perf_counter() - t
        line["ok"] = line["ok"] and _all_kernel(line)
        print(json.dumps(line), flush=True)
        ok = ok and line["ok"]
    if not ok:
        raise SystemExit("chip_smoke: a phase failed its checks")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
