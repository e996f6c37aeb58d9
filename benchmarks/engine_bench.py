"""Engine throughput: python-loop driver vs fully-jitted scan engine, plus
rounds/sec scaling of the mesh-sharded engine over fake host devices.

Measures communication rounds/sec at fleet sizes N in {12, 128, 512, 2048}
for (a) the per-round python driver — one jitted ``step`` dispatch per
round, each round's outputs copied to the host before the next, as
``FedARServer`` drives it — and (b) the ``lax.scan`` engine, which
compiles once and keeps all R rounds on-device.  Compile time is reported
separately (``compile_sec``) from steady-state rounds/sec: the first run
(compile + warm-up) is excluded from the timed repeats, and the steady
number is the median over repeats (3 in ``--quick`` — the repeat-median the
CI perf gate leans on against runner jitter).

The ``--devices`` dimension re-runs the scan engine with
``FedConfig.mesh_shape=k`` for each requested device count, so one
invocation records the 1-vs-k scaling curve.  On the CPU backend every
count spawns a worker process with ``XLA_FLAGS=--xla_force_host_platform_
device_count=k`` (the flag must land before jax initializes); on an
accelerator, whose chips belong to one process, every count runs in this
process over the first k devices.

The ``defense`` axis re-runs the scan engine per robust-defense strategy
(none vs dense foolsgold vs the sketched cluster-aware variant), pricing
the O(N*D) dense similarity gather against the (N, r) sketch.  The
``scenario`` axis re-runs it per non-IID data scenario through the
engine's AUTO layout pick (``FederatedDataset.engine_arrays`` — heavy
quantity skew gets the packed bucketed layout, near-uniform fleets the
dense rectangle), at an equal per-client sample budget; ``dense`` keeps
the legacy wrap-padded fleet as the baseline.  The ``gated`` axis prices
LAYOUT x GATING on ONE fixed quantity-skew fleet: ``dense_full`` /
``dense_gated`` pay the rectangular pad-to-max layout, ``packed_full`` /
``packed_gated`` the bucketed packed layout, and ``dense_gated`` vs
``packed_gated`` isolates what the two-pass global cohort saves.  (The
old axis compared the packed modes on a skewed fleet against dense modes
on a UNIFORM fleet — a cross-dataset number that made the packed layout
look like a tax; same-fleet is the honest layout comparison, and the
perf gate enforces the ``packed_* >= dense_*`` win condition on it.)
The ``model_family`` axis runs the same scan engine per client family — the
paper's MNIST MLP vs a reduced transformer LM behind the ``ClientModel``
boundary — so the gate also covers the pytree flatten/unflatten aggregation
path.  The ``cohort`` axis prices the host-store cohort engine
(``FedConfig.cohort_size``) at fleet sizes up to 1M clients x cohort sizes
K — store-build time separate from steady rounds/sec — plus an in-run
``resident`` N=2048 ceiling the gate's cohort win condition leans on.
The ``compress`` axis prices the uplink-compression modes (qsgd 8/4-bit
stochastic quantization, magnitude top-k, vs the dense baseline) inside
the same jitted scan, recording payload bytes/client next to the dense
4*D so the gate can enforce the nominal compression ratios intra-run.
The ``faults`` axis prices the chaos fault schedule (seeded per-round
draw + corrupt-row rewrite + non-finite quarantine) against the
fault-free engine at the same config — an intra-run pair the gate bounds
at <= 10% overhead.

Run:  PYTHONPATH=src python -m benchmarks.engine_bench [--quick]
                                                       [--devices 1,8]
Emits ``BENCH_engine.json`` (rounds/sec + compile_sec per fleet size, per
device count, per defense strategy, per data scenario and per gating mode)
for the perf trajectory; also wired into ``benchmarks.run`` and gated by
``benchmarks.perf_gate`` in CI.
"""
from __future__ import annotations

import json
import os
import statistics
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.fedar_mnist import fleet_fed, small_model
from repro.core.engine import FedAREngine
from repro.core.resources import TaskRequirement
from repro.data.datasets import make_federated
from repro.data.federated import scaled_fleet

FLEET_SIZES = (12, 128, 512, 2048)
QUICK_SIZES = (12, 128)
SHARDED_SIZES = (128, 512, 2048)
QUICK_SHARDED_SIZES = (128,)
DEVICE_COUNTS = (1, 8)
DEFENSES = ("none", "foolsgold", "foolsgold_sketch")
DEFENSE_SIZES = (128, 512)
QUICK_DEFENSE_SIZES = (128,)
SCENARIOS = ("dense", "iid", "label_skew", "quantity_skew", "robot_drift")
SCENARIO_SIZES = (128, 512, 2048)
QUICK_SCENARIO_SIZES = (128,)
GATED_SIZES = (128, 512)
QUICK_GATED_SIZES = (128,)
GATED_FRAC = 0.5  # = client_fraction: cohort exactly covers the selection
MODEL_FAMILY_SIZES = (12,)
COHORT_FLEETS = (2048, 65536, 1_000_000)
QUICK_COHORT_FLEETS = (2048, 65536)
COHORT_SIZES = (256, 512)
QUICK_COHORT_SIZES = (512,)
COHORT_WIN_N = 2048  # fleet whose resident ceiling is re-measured in-run
COMPRESS_SIZES = (128, 512)
QUICK_COMPRESS_SIZES = (128,)
# uplink compression modes priced against the dense baseline; each leaf also
# records payload_bytes_per_client vs dense_bytes_per_client (4 * D), the
# intra-run pair the perf gate's compress win condition checks against the
# nominal ratios (qsgd-8 <= 1/2, qsgd-4 <= 1/4, topk <= 1/2 of dense).
COMPRESS_MODES = (
    ("none", {}),
    ("qsgd8", dict(compress="qsgd", compress_bits=8)),
    ("qsgd4", dict(compress="qsgd", compress_bits=4)),
    ("topk", dict(compress="topk")),  # compress_k=None -> D // 32
)
FAULT_SIZES = (128,)
# the chaos schedule vs the fault-free engine on the SAME config: the
# per-round fault draw + quarantine run inside the jitted scan, and the
# perf gate's faults win condition bounds their overhead at 10% intra-run
FAULT_MODES = (
    ("none", {}),
    ("chaos", dict(faults="chaos")),
)
SAMPLES = 20  # one local batch per client per round keeps dispatch dominant
QUICK_REPEATS = 3  # repeat-median absorbs CI runner jitter
FULL_REPEATS = 2


def _make(n: int, *, mesh_shape: int | None = None, defense: str = "none",
          scenario: str | None = None, select_frac: float | None = None,
          layout: str = "auto", **fed_kw):
    fed = fleet_fed(n, local_epochs=1, local_batch_size=20, defense=defense,
                    mesh_shape=mesh_shape, select_frac=select_frac, **fed_kw)
    engine = FedAREngine(small_model(32), fed, TaskRequirement())
    if scenario is None or scenario == "dense":
        raw = scaled_fleet(n, samples_per_client=SAMPLES)
    else:
        # same per-client sample budget as the dense baseline, through the
        # engine's auto layout pick (default): near-uniform scenarios keep
        # the dense rectangle, heavy quantity skew gets the bucketed packed
        # layout (<= 2x, batch-quantized pad-to-bucket residual).  An
        # explicit ``layout`` pins one side of the pick (the gated axis
        # prices dense vs packed on the same fleet).
        raw = make_federated(
            "digits", n, scenario=scenario, samples_per_client=SAMPLES
        ).engine_arrays(shards=engine.comms.shards,
                        quantum=fed.local_batch_size, layout=layout)
    data = jax.tree.map(jnp.asarray, raw)
    return engine, data


def _time_python(engine, data, rounds: int) -> float:
    def one_round(state):
        state, out = engine.step(state, data)
        jax.tree.map(np.asarray, out)  # the per-round host round-trip
        return state

    # one untimed round absorbs compile and first-touch costs
    state = one_round(engine.init_state())
    t0 = time.perf_counter()
    for _ in range(rounds):
        state = one_round(state)
    return (time.perf_counter() - t0) / rounds


def _time_scan(engine, data, rounds: int, repeats: int = FULL_REPEATS) -> dict:
    """{"rounds_per_sec": steady-state median, "compile_sec": first-call
    wall time minus the steady cost of its rounds} — compile and warm-up
    never pollute the throughput number."""
    state = engine.init_state()
    t0 = time.perf_counter()
    jax.block_until_ready(engine.run(state, data, rounds=rounds))
    first = time.perf_counter() - t0
    times = []
    for _ in range(max(1, repeats)):
        t0 = time.perf_counter()
        jax.block_until_ready(engine.run(state, data, rounds=rounds))
        times.append((time.perf_counter() - t0) / rounds)
    steady = statistics.median(times)
    return {
        "rounds_per_sec": 1.0 / steady,
        "compile_sec": round(max(0.0, first - rounds * steady), 3),
    }


def _repeats(quick: bool) -> int:
    return QUICK_REPEATS if quick else FULL_REPEATS


def bench(quick: bool = False):
    """Returns (csv rows, per-fleet-size summary dict)."""
    rows, summary = [], {}
    for n in QUICK_SIZES if quick else FLEET_SIZES:
        engine, data = _make(n)
        # keep wall time sane as the fleet grows
        r_py = max(2, 8 // max(1, n // 128))
        r_scan = max(4, 16 // max(1, n // 512))
        s_py = _time_python(engine, data, r_py)
        scan = _time_scan(engine, data, r_scan, repeats=_repeats(quick))
        rps_py, rps_scan = 1.0 / s_py, scan["rounds_per_sec"]
        speedup = rps_scan / rps_py
        rows.append((f"engine_python_N{n}", round(s_py * 1e6, 1),
                     round(rps_py, 2)))
        rows.append((f"engine_scan_N{n}", round(1e6 / rps_scan, 1),
                     round(rps_scan, 2)))
        rows.append((f"engine_speedup_N{n}", 0.0, round(speedup, 2)))
        summary[str(n)] = {
            "python_rounds_per_sec": rps_py,
            "scan_rounds_per_sec": rps_scan,
            "scan_compile_sec": scan["compile_sec"],
            "speedup": speedup,
        }
    return rows, summary


def bench_sharded_worker(device_count: int, quick: bool) -> dict:
    """In-process sharded measurement; assumes the host already exposes
    ``device_count`` devices (the parent sets XLA_FLAGS before spawning)."""
    out = {}
    mesh = device_count if device_count > 1 else None
    for n in QUICK_SHARDED_SIZES if quick else SHARDED_SIZES:
        engine, data = _make(n, mesh_shape=mesh)
        out[str(n)] = _time_scan(engine, data, rounds=8,
                                 repeats=_repeats(quick))
    return out


def bench_defense(quick: bool = False) -> dict:
    """rounds/sec of the scan engine per defense strategy: the cost of the
    dense (N, D) FoolsGold gather vs the (N, r) sketch vs no defense."""
    out = {}
    for n in QUICK_DEFENSE_SIZES if quick else DEFENSE_SIZES:
        out[str(n)] = {}
        for defense in DEFENSES:
            engine, data = _make(n, defense=defense)
            out[str(n)][defense] = _time_scan(engine, data, rounds=4,
                                              repeats=_repeats(quick))
    return out


def bench_scenario(quick: bool = False) -> dict:
    """rounds/sec of the scan engine per data scenario: the dense wrap-
    padded fleet vs the packed bucketed layout per non-IID scenario."""
    out = {}
    for n in QUICK_SCENARIO_SIZES if quick else SCENARIO_SIZES:
        out[str(n)] = {}
        for scenario in SCENARIOS:
            engine, data = _make(n, scenario=scenario)
            out[str(n)][scenario] = _time_scan(engine, data, rounds=4,
                                               repeats=_repeats(quick))
    return out


GATED_MODES = (
    ("dense_full", "dense", None),
    ("dense_gated", "dense", GATED_FRAC),
    ("packed_full", "packed", None),
    ("packed_gated", "packed", GATED_FRAC),
)


def bench_gated(quick: bool = False) -> dict:
    """Layout x gating on ONE quantity-skew fleet: the rectangular
    pad-to-max layout vs the bucketed packed layout, each full-N and
    selection-gated (``select_frac``; gated runs the two-pass global
    cohort on the packed side).  Same fleet for all four modes, so
    ``packed_* >= dense_*`` is the layout win condition the perf gate
    enforces — the packed layout must strictly dominate dense on the
    skewed fleets the auto pick routes to it."""
    out = {}
    for n in QUICK_GATED_SIZES if quick else GATED_SIZES:
        out[str(n)] = {}
        for mode, layout, frac in GATED_MODES:
            engine, data = _make(n, scenario="quantity_skew",
                                 select_frac=frac, layout=layout)
            out[str(n)][mode] = _time_scan(engine, data, rounds=8,
                                           repeats=_repeats(quick))
    return out


def bench_model_family(quick: bool = False) -> dict:
    """rounds/sec of the scan engine per client-model family: the paper's
    MNIST MLP vs a reduced transformer LM behind the same ``ClientModel``
    boundary — the perf gate covers the pytree flatten/unflatten
    aggregation path, not just the flat MLP hot path."""
    from repro.configs import get_config
    from repro.data.pipeline import federated_lm_corpus
    from repro.models.model import LMClientModel

    out = {}
    for n in MODEL_FAMILY_SIZES:
        out[str(n)] = {}
        engine, data = _make(n)
        out[str(n)]["mnist_mlp"] = _time_scan(engine, data, rounds=4,
                                              repeats=_repeats(quick))
        cfg = get_config("tinyllama-1.1b").reduced(
            num_layers=1, d_model=64, d_ff=128, vocab_size=128,
            num_heads=2, num_kv_heads=1,
        )
        fed = fleet_fed(n, local_epochs=1, local_batch_size=4,
                        defense="none")
        lm_engine = FedAREngine(LMClientModel(cfg), fed, TaskRequirement())
        raw, _meta = federated_lm_corpus(
            n, vocab=cfg.vocab_size, seq=32, samples_per_client=8, topics=4
        )
        lm_data = jax.tree.map(jnp.asarray, raw)
        out[str(n)]["lm"] = _time_scan(lm_engine, lm_data, rounds=4,
                                       repeats=_repeats(quick))
    return out


def _time_cohort(server, fleet, rounds: int, repeats: int) -> dict:
    """Cohort-mode steady rounds/sec: the first (compile + first-touch)
    round is excluded, then the median per-round cost over ``repeats``
    timed batches.  Rounds keep advancing the store — each batch samples
    fresh cohorts, so the number prices the real per-round pipeline
    (host sampling + gather + jitted step + scatter)."""
    t0 = time.perf_counter()
    server.run(fleet, 1)
    first = time.perf_counter() - t0
    times = []
    for _ in range(max(1, repeats)):
        t0 = time.perf_counter()
        server.run(fleet, rounds)
        times.append((time.perf_counter() - t0) / rounds)
    steady = statistics.median(times)
    return {
        "rounds_per_sec": 1.0 / steady,
        "compile_sec": round(max(0.0, first - steady), 3),
    }


def bench_cohort(quick: bool = False) -> dict:
    """Host-store cohort engine: steady rounds/sec at fleet sizes N the
    resident engine cannot hold x cohort sizes K, with the one-time store
    build (host O(N) numpy tables + sub-engine init) reported separately
    (``store_build_sec``).  The ``resident`` leaf re-measures the resident
    scan engine at N=2048 in the SAME process — the intra-run ceiling the
    perf gate's cohort win condition compares K=512 against (the cohort
    engine does strictly less per-round work, so it must not lose)."""
    from repro.core.fedar import FedARServer
    from repro.data.datasets import VirtualFleet

    out = {}
    rounds = 4 if quick else 8
    for n in QUICK_COHORT_FLEETS if quick else COHORT_FLEETS:
        out[str(n)] = {}
        fleet = VirtualFleet(n, samples_per_client=SAMPLES)
        for k in QUICK_COHORT_SIZES if quick else COHORT_SIZES:
            t0 = time.perf_counter()
            fed = fleet_fed(n, local_epochs=1, local_batch_size=20,
                            defense="none", cohort_size=k)
            server = FedARServer(small_model(32), fed, TaskRequirement())
            build = time.perf_counter() - t0
            leaf = _time_cohort(server, fleet, rounds, _repeats(quick))
            leaf["store_build_sec"] = round(build, 3)
            out[str(n)][f"K{k}"] = leaf
    engine, data = _make(COHORT_WIN_N)
    out[str(COHORT_WIN_N)]["resident"] = _time_scan(
        engine, data, rounds=4, repeats=_repeats(quick)
    )
    return out


def bench_compress(quick: bool = False) -> dict:
    """rounds/sec of the scan engine per uplink compression mode, plus the
    payload accounting the gate's compress win condition checks: each leaf
    carries ``payload_bytes_per_client`` (the strategy's encoded uplink
    size) next to ``dense_bytes_per_client`` (4 * D fp32) — measured
    intra-run, so the nominal-ratio check needs no machine calibration.
    The quantize/pack work rides inside the same jitted scan, so the
    rounds/sec leaves also feed the ordinary regression comparison."""
    out = {}
    for n in QUICK_COMPRESS_SIZES if quick else COMPRESS_SIZES:
        out[str(n)] = {}
        for mode, kw in COMPRESS_MODES:
            engine, data = _make(n, **kw)
            leaf = _time_scan(engine, data, rounds=4,
                              repeats=_repeats(quick))
            leaf["payload_bytes_per_client"] = int(
                engine.compression.payload_nbytes(engine.dim)
            )
            leaf["dense_bytes_per_client"] = 4 * engine.dim
            out[str(n)][mode] = leaf
    return out


def bench_faults(quick: bool = False) -> dict:
    """rounds/sec of the scan engine with the chaos fault schedule vs the
    fault-free engine at the same config: the seeded per-round draw, the
    corrupt-row rewrite and the always-on non-finite quarantine all ride
    inside the jitted scan, so their cost is one intra-run pair the perf
    gate bounds (chaos >= 0.9 * none)."""
    out = {}
    for n in FAULT_SIZES:
        out[str(n)] = {}
        for mode, kw in FAULT_MODES:
            engine, data = _make(n, **kw)
            out[str(n)][mode] = _time_scan(engine, data, rounds=4,
                                           repeats=_repeats(quick))
    return out


def bench_devices(quick: bool = False, counts=DEVICE_COUNTS) -> dict:
    """rounds/sec of the scan engine per device count.  On the CPU backend
    each count gets a worker process, so its XLA host-device flag precedes
    jax init.  On an accelerator this process already holds the chips, and
    a child could not open them: every count runs here, over
    ``jax.devices()[:k]``."""
    if jax.default_backend() != "cpu":
        return {str(k): bench_sharded_worker(k, quick) for k in counts}
    result = {}
    for k in counts:
        env = dict(os.environ)
        env["XLA_FLAGS"] = (
            env.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={k}"
        ).strip()
        cmd = [sys.executable, "-m", "benchmarks.engine_bench",
               "--worker", str(k)]
        if quick:
            cmd.append("--quick")
        proc = subprocess.run(cmd, capture_output=True, text=True, env=env)
        if proc.returncode != 0:
            raise RuntimeError(
                f"devices={k} worker failed "
                f"(exit {proc.returncode}):\n{proc.stderr.strip()[-2000:]}"
            )
        result[str(k)] = json.loads(proc.stdout.strip().splitlines()[-1])
    return result


def write_json(summary, devices=None, defense=None, scenario=None,
               gated=None, model_family=None, cohort=None, compress=None,
               faults=None, path: str = "BENCH_engine.json") -> None:
    payload = {"rounds_per_sec": summary}
    if devices is not None:
        payload["sharded_rounds_per_sec_by_devices"] = devices
    if defense is not None:
        payload["defense_rounds_per_sec"] = defense
    if scenario is not None:
        payload["scenario_rounds_per_sec"] = scenario
    if gated is not None:
        payload["gated_rounds_per_sec"] = gated
    if model_family is not None:
        payload["model_family_rounds_per_sec"] = model_family
    if cohort is not None:
        payload["cohort_rounds_per_sec"] = cohort
    if compress is not None:
        payload["compress_rounds_per_sec"] = compress
    if faults is not None:
        payload["faults_rounds_per_sec"] = faults
    with open(path, "w") as f:
        json.dump(payload, f, indent=2)


def _rps(entry) -> float:
    """rounds/sec from a bench leaf (dict schema or a legacy float) — the
    one schema decoder, shared with the CI gate."""
    from benchmarks.perf_gate import _rps as gate_rps

    val = gate_rps(entry)
    if val is None:
        raise ValueError(f"not a bench throughput leaf: {entry!r}")
    return val


def _parse_counts(argv) -> tuple:
    if "--devices" in argv:
        raw = argv[argv.index("--devices") + 1]
        return tuple(int(c) for c in raw.split(","))
    return DEVICE_COUNTS


def main() -> None:
    argv = sys.argv[1:]
    quick = "--quick" in argv
    if "--worker" in argv:  # child: measure one device count, emit JSON
        k = int(argv[argv.index("--worker") + 1])
        assert len(jax.devices()) >= k or k == 1, "worker missing devices"
        print(json.dumps(bench_sharded_worker(k, quick)))
        return
    rows, summary = bench(quick=quick)
    devices = bench_devices(quick=quick, counts=_parse_counts(argv))
    defense = bench_defense(quick=quick)
    scenario = bench_scenario(quick=quick)
    gated = bench_gated(quick=quick)
    family = bench_model_family(quick=quick)
    cohort = bench_cohort(quick=quick)
    compress = bench_compress(quick=quick)
    faults = bench_faults(quick=quick)
    write_json(summary, devices, defense, scenario, gated, family, cohort,
               compress, faults)
    for k, per_n in devices.items():
        for n, v in per_n.items():
            rows.append((f"engine_scan_N{n}_dev{k}", round(1e6 / _rps(v), 1),
                         round(_rps(v), 2)))
    for n, per_d in defense.items():
        for d, v in per_d.items():
            rows.append((f"engine_scan_N{n}_{d}", round(1e6 / _rps(v), 1),
                         round(_rps(v), 2)))
    for n, per_s in scenario.items():
        for s, v in per_s.items():
            rows.append((f"engine_scan_N{n}_data_{s}",
                         round(1e6 / _rps(v), 1), round(_rps(v), 2)))
    for n, per_g in gated.items():
        for g, v in per_g.items():
            rows.append((f"engine_scan_N{n}_sgd_{g}",
                         round(1e6 / _rps(v), 1), round(_rps(v), 2)))
    for n, per_f in family.items():
        for fam, v in per_f.items():
            rows.append((f"engine_scan_N{n}_model_{fam}",
                         round(1e6 / _rps(v), 1), round(_rps(v), 2)))
    for n, per_k in cohort.items():
        for k, v in per_k.items():
            rows.append((f"engine_cohort_N{n}_{k}",
                         round(1e6 / _rps(v), 1), round(_rps(v), 2)))
    for n, per_c in compress.items():
        for mode, v in per_c.items():
            rows.append((f"engine_scan_N{n}_compress_{mode}",
                         round(1e6 / _rps(v), 1), round(_rps(v), 2)))
    for n, per_f in faults.items():
        for mode, v in per_f.items():
            rows.append((f"engine_scan_N{n}_faults_{mode}",
                         round(1e6 / _rps(v), 1), round(_rps(v), 2)))
    print("name,us_per_round,rounds_per_sec_or_speedup")
    for name, us, derived in rows:
        print(f"{name},{us},{derived}")


if __name__ == "__main__":
    main()
