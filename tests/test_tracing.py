"""The program's own tracing (``common/tracing.py``): every device phase
scope reaches the lowered round, the host spans of a round nest under
``fedar.round`` with their stats, and tracing leaves the numbers alone."""
import re
import tempfile
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import FedARServer
from repro.common import tracing
from repro.configs.fedar_mnist import fleet_fed, small_model
from repro.core.resources import TaskRequirement
from repro.data.datasets import VirtualFleet, make_federated

REQ = TaskRequirement()
N = 16
# the server's preparation (its data), then the engine's (checks, layout,
# FLOPs), the jitted call, the device's finish, the copies, the history
ROUND_CHILDREN = ["fedar.prepare", "fedar.prepare", "fedar.dispatch",
                  "fedar.wait", "fedar.fetch", "fedar.history"]
# the two routes: packed + gated + top-k + sketched defense, and the dense
# rectangle with the qsgd codec and dense FoolsGold; chaos faults and an
# eval set in both, so every phase has work
ROUTES = {
    "packed_topk_sketch": dict(layout="packed", select_frac=0.5,
                               compress="topk", defense="foolsgold_sketch",
                               defense_sketch_dim=32),
    "dense_qsgd": dict(layout="dense", compress="qsgd", defense="foolsgold"),
}


def _server(layout="packed", **kw):
    kw.setdefault("local_epochs", 1)
    fed = fleet_fed(N, **kw)
    server = FedARServer(small_model(8), fed, REQ)
    ds = make_federated("digits", N, scenario="quantity_skew",
                        samples_per_client=40)
    data = server.engine.prepare_data(ds, layout=layout)
    assert ("packed" in data) == (layout == "packed")
    return server, data


def _eval_set():
    rng = np.random.default_rng(0)
    return (jnp.asarray(rng.normal(size=(32, 784)), jnp.float32),
            jnp.asarray(rng.integers(0, 10, 32), jnp.int32))


def _program_spans(fn):
    """Run ``fn`` under the profiler; returns its result and the program's
    host spans as ``(start, end, name, stats)``, sorted by start."""
    from jax.profiler import ProfileData

    trace_dir = tempfile.mkdtemp(prefix="tracing_test_")
    jax.profiler.start_trace(trace_dir)
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    (pb,) = Path(trace_dir).rglob("*.xplane.pb")
    spans = []
    for plane in ProfileData.from_file(str(pb)).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if re.fullmatch(r"(fedar|cohort)\.[\w.]+", ev.name):
                    spans.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                  ev.name, dict(ev.stats)))
    return out, sorted(spans)


def _children(spans, parent):
    """The spans directly inside ``parent`` (no program span between)."""
    s0, e0 = parent[0], parent[1]
    inside = [s for s in spans if s is not parent and s0 <= s[0]
              and s[1] <= e0]
    return [s for s in inside
            if not any(o is not s and o[0] <= s[0] and s[1] <= o[1]
                       for o in inside)]


@pytest.mark.parametrize("route", sorted(ROUTES))
def test_every_phase_scope_is_in_the_lowered_round(route):
    server, data = _server(faults="chaos", **ROUTES[route])
    text = server.engine.lower_step(server.state, data,
                                    eval_set=_eval_set()).as_text(
                                        debug_info=True)
    names = {part for loc in re.findall(r'loc\("([^"]*)"', text)
             for part in re.split(r"[/()]", loc)}
    missing = [p for p in tracing.PHASES if p not in names]
    assert not missing, f"phase scopes missing from the {route} round"


def test_unknown_phase_is_refused():
    with pytest.raises(ValueError, match="unknown phase"):
        tracing.phase("codec")


def test_compile_count_counts_compiles():
    x = jnp.arange(7.0).block_until_ready()
    f = jax.jit(lambda x: x * 3 + 1)
    before = tracing.compile_count()
    f(x).block_until_ready()
    assert tracing.compile_count() == before + 1
    f(x).block_until_ready()
    assert tracing.compile_count() == before + 1


def test_resident_round_spans_and_stats():
    server, data = _server(**ROUTES["packed_topk_sketch"])
    server.run_round(data)  # compiles
    (selected, on_time), spans = _program_spans(
        lambda: server.run_round(data))
    (rnd,) = [s for s in spans if s[2] == "fedar.round"]
    kids = _children(spans, rnd)
    assert [s[2] for s in kids] == ROUND_CHILDREN
    stats = {s[2]: s[3] for s in kids}
    assert stats["fedar.dispatch"]["compiles"] == 0
    fetch = stats["fedar.fetch"]
    # six history outputs and the codec's count: (N,) f32 trust, two (N,)
    # bool masks, four () scalars
    assert fetch["copies"] == 7
    assert fetch["bytes"] == N * 4 + 2 * N + 3 * 4 + 4
    # fedar aggregation: the on-time selected clients transmit
    assert fetch["codec_rows_sent"] == int(np.sum(selected & on_time))
    assert fetch["codec_rows_encoded"] == N


def test_codec_counter_absent_without_codec():
    server, data = _server(compress="none")
    server.run_round(data)
    _, spans = _program_spans(lambda: server.run_round(data))
    (fetch,) = [s for s in spans if s[2] == "fedar.fetch"]
    assert fetch[3]["copies"] == 6
    assert "codec_rows_sent" not in fetch[3]


def test_cohort_round_spans_nest_under_the_round():
    fed = fleet_fed(48, cohort_size=8, local_epochs=1,
                    defense="foolsgold_sketch", defense_sketch_dim=32,
                    compress="topk")
    fleet = VirtualFleet(48, samples_per_client=40, seed=0)
    server = FedARServer(small_model(8), fed, REQ)
    server.run_round(fleet)
    _, spans = _program_spans(lambda: server.run_round(fleet))
    (rnd,) = [s for s in spans if s[2] == "fedar.round"]
    kids = _children(spans, rnd)
    assert [s[2] for s in kids] == [
        "cohort.sample", "cohort.arrays", "cohort.gather", "cohort.h2d",
        "cohort.step", "fedar.wait", "cohort.scatter", "cohort.finish",
        "fedar.fetch", "fedar.history"]
    by_name = {s[2]: s for s in kids}
    assert {s[2] for s in _children(spans, by_name["cohort.step"])} == {
        "fedar.prepare", "fedar.dispatch"}
    assert by_name["cohort.scatter"][3]["bytes"] > 0
    assert by_name["fedar.fetch"][3]["codec_rows_encoded"] == 8


def test_tracing_leaves_the_outputs_alone():
    servers = [_server(**ROUTES["packed_topk_sketch"]) for _ in range(2)]
    ev = _eval_set()
    (server0, data0), (server1, data1) = servers
    for _ in range(2):
        server0.run_round(data0, eval_set=ev)
        _program_spans(lambda: server1.run_round(data1, eval_set=ev))
    for key in ("trust", "selected", "on_time", "loss", "acc"):
        np.testing.assert_array_equal(np.asarray(server0.history[key]),
                                      np.asarray(server1.history[key]))
    for a, b in zip(jax.tree.leaves(server0.state),
                    jax.tree.leaves(server1.state)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_scan_run_fetch_counts_every_round():
    server, data = _server(**ROUTES["packed_topk_sketch"])
    server.run(data, 2)  # compiles
    _, spans = _program_spans(lambda: server.run(data, 2))
    (fetch,) = [s for s in spans if s[2] == "fedar.fetch"]
    assert fetch[3]["copies"] == 7
    assert fetch[3]["codec_rows_encoded"] == 2 * N
    sent = np.asarray(server.history["selected"][-2:]) & np.asarray(
        server.history["on_time"][-2:])
    assert fetch[3]["codec_rows_sent"] == int(sent.sum())
