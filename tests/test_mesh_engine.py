"""Mesh-sharded engine equivalence: the shard_map path over the ``clients``
axis must reproduce the single-device engine (trust history, selection
masks, final params) within fp32 tolerance.

Runs under ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (the CI
mesh job); with fewer than 8 devices every test skips.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.fedar_mnist import fleet_fed, small_model
from repro.core.engine import FedAREngine
from repro.core.fedar import FedARServer
from repro.core.resources import TaskRequirement
from repro.data.federated import scaled_fleet
from repro.data.synthetic import make_digits

SHARDS = 8
N = 128
ROUNDS = 4

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < SHARDS,
    reason=f"needs {SHARDS} devices "
    f"(XLA_FLAGS=--xla_force_host_platform_device_count={SHARDS})",
)

_DATA_CACHE = {}


def _data(n=N, samples=40):
    if (n, samples) not in _DATA_CACHE:
        _DATA_CACHE[(n, samples)] = {
            k: jnp.asarray(v)
            for k, v in scaled_fleet(n, samples_per_client=samples).items()
        }
    return _DATA_CACHE[(n, samples)]


def _engines(aggregation, n=N, foolsgold=False, defense=None, **extra):
    kw = dict(local_epochs=1, foolsgold=foolsgold, aggregation=aggregation,
              **extra)
    if defense is not None:
        kw["defense"] = defense
    e1 = FedAREngine(small_model(32), fleet_fed(n, **kw), TaskRequirement())
    e8 = FedAREngine(
        small_model(32), fleet_fed(n, mesh_shape=SHARDS, **kw),
        TaskRequirement(),
    )
    assert e8.mesh is not None and e8.mesh.devices.size == SHARDS
    return e1, e8


def _assert_equivalent(e1, e8, data, *, eval_set=None):
    s1, o1 = e1.run(e1.init_state(), data, rounds=ROUNDS, eval_set=eval_set)
    s8, o8 = e8.run(e8.init_state(), data, rounds=ROUNDS, eval_set=eval_set)
    # (N,) bookkeeping is replicated in the sharded program -> exact
    np.testing.assert_array_equal(np.asarray(o1.selected),
                                  np.asarray(o8.selected))
    np.testing.assert_array_equal(np.asarray(o1.on_time),
                                  np.asarray(o8.on_time))
    np.testing.assert_allclose(np.asarray(o1.trust), np.asarray(o8.trust),
                               atol=1e-4)
    # params differ only by psum reduction order -> fp32 tolerance
    np.testing.assert_allclose(np.asarray(s1.params), np.asarray(s8.params),
                               atol=1e-4, rtol=1e-4)
    if eval_set is not None:
        np.testing.assert_allclose(np.asarray(o1.acc), np.asarray(o8.acc),
                                   atol=1e-3)
    return s1, s8


@pytest.mark.parametrize("mode", ["fedar", "fedavg", "async"])
def test_sharded_matches_single_device(mode):
    """Acceptance bar: N=128, 8 client shards, all aggregation modes."""
    e1, e8 = _engines(mode)
    ex, ey = make_digits(200, seed=99)
    _assert_equivalent(e1, e8, _data(), eval_set=(ex, ey))


def test_sharded_async_buffer_state_matches():
    """The buffered-async carry (slots, tags) is replicated bookkeeping and
    must come back identical from the sharded program."""
    e1, e8 = _engines("async")
    s1, s8 = _assert_equivalent(e1, e8, _data())
    for f in ("pending_weight", "pending_issued", "pending_arrival",
              "pending_valid"):
        np.testing.assert_array_equal(np.asarray(getattr(s1, f)),
                                      np.asarray(getattr(s8, f)))


def test_sharded_rounds_compile_once():
    """Round inputs are laid out on the mesh before the jitted round, so
    the sharded state a round returns matches the layout the first round
    compiled for (no second compile), and prepared data lands in shards."""
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro import make_federated

    _, e8 = _engines("fedar", n=64)
    data = e8.prepare_data(
        make_federated("digits", 64, samples_per_client=20)
    )
    x = data["x"]
    assert x.sharding == NamedSharding(e8.mesh, P("clients"))
    state = e8.init_state()
    for _ in range(3):
        state, _ = e8.step(state, data)
    assert e8._step._cache_size() == 1


def test_sharded_foolsgold_gathered_product_matches():
    """FoolsGold's gathered block similarity == the dense (N, N) matrix."""
    e1, e8 = _engines("fedar", n=64, foolsgold=True)
    _assert_equivalent(e1, e8, _data(n=64))


def test_sharded_sketch_defense_matches_single_device():
    """The cluster-aware sketched defense: 8 client shards reproduce the
    single-device sketch path to fp32 tolerance, and the cross-shard
    defense payload is the (N, r) sketch — never the dense (N, D) history
    (asserted via the gather_defense shape instrumentation)."""
    n = 64
    e1, e8 = _engines("fedar", n=n, defense="foolsgold_sketch")
    _assert_equivalent(e1, e8, _data(n=n))
    r, d = e8.fed.defense_sketch_dim, e8.dim
    assert r < d
    for comms in (e1.comms, e8.comms):
        shapes = comms.defense_gather_shapes
        assert shapes, "defense gather never traced"
        assert all(s == (n, r) for s in shapes), shapes


def test_sharded_dense_defense_gathers_full_history():
    """Contrast fixture for the payload instrumentation: the dense strategy
    really does ship (N, D) across the mesh — the O(N*D) footprint the
    sketch variant removes."""
    n = 64
    _, e8 = _engines("fedar", n=n, foolsgold=True)
    e8.run(e8.init_state(), _data(n=n), rounds=1)
    assert (n, e8.dim) in e8.comms.defense_gather_shapes


@pytest.mark.parametrize(
    "kw", [dict(compress="qsgd", compress_bits=8),
           dict(compress="qsgd", compress_bits=4),
           dict(compress="topk", compress_k=256)],
)
def test_sharded_compressed_matches_single_device(kw):
    """Compressed runs match 1 vs 8 devices: quantization bits are keyed
    on the CANONICAL client id, so the stochastic codes are identical
    across shardings and only psum order (plus the rare code flip at an
    fp32 ulp boundary, worth ~scale/L) separates the trajectories.  The
    recorded uplink payload must be the packed wire format — shard-local
    uint8 codes / (k,) pairs — never re-densified fp32."""
    n = 64
    e1, e8 = _engines("fedar", n=n, defense="foolsgold_sketch", **kw)
    s1, o1 = e1.run(e1.init_state(), _data(n=n), rounds=ROUNDS)
    s8, o8 = e8.run(e8.init_state(), _data(n=n), rounds=ROUNDS)
    np.testing.assert_array_equal(np.asarray(o1.selected),
                                  np.asarray(o8.selected))
    np.testing.assert_array_equal(np.asarray(o1.on_time),
                                  np.asarray(o8.on_time))
    np.testing.assert_allclose(np.asarray(o1.trust), np.asarray(o8.trust),
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(s1.params), np.asarray(s8.params),
                               atol=1e-3, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(s1.compress_residual),
                               np.asarray(s8.compress_residual),
                               atol=1e-2, rtol=1e-2)
    for comms, rows in ((e1.comms, n), (e8.comms, n // SHARDS)):
        shapes = comms.uplink_payload_shapes
        assert shapes, "compressed uplink never traced"
        for leaves in shapes:
            if kw["compress"] == "qsgd":
                (cshape, cdtype), (sshape, sdtype) = leaves
                assert cdtype == "uint8" and cshape[0] == rows
                assert cshape[1] == -(-e8.dim * kw["compress_bits"] // 8)
                assert sshape == (rows, 1) and sdtype == "float32"
            else:
                assert {s for s, _ in leaves} == {(rows, kw["compress_k"])}
                assert {d for _, d in leaves} == {"int32", "float32"}


def test_sharded_uncompressed_records_no_uplink():
    """compress="none" never hits the payload instrumentation — the
    uncompressed engine must not even trace the roundtrip."""
    e1, e8 = _engines("fedar", n=64, defense="foolsgold_sketch")
    e8.run(e8.init_state(), _data(n=64), rounds=1)
    assert e8.comms.uplink_payload_shapes == []


def test_sharded_server_api_unchanged():
    """FedARServer keeps its API on a mesh: same history layout, and the
    host-visible rows match the unsharded server."""
    fed = fleet_fed(N, local_epochs=1, foolsgold=False, mesh_shape=SHARDS)
    srv = FedARServer(small_model(32), fed, TaskRequirement())
    ref = FedARServer(
        small_model(32), fleet_fed(N, local_epochs=1, foolsgold=False),
        TaskRequirement(),
    )
    assert srv.mesh is not None and ref.mesh is None
    data = _data()
    srv.run_round(data)  # per-round driver crosses the shard_map too
    srv.run(data, rounds=2)
    ref.run(data, rounds=3)
    np.testing.assert_allclose(np.stack(srv.history["trust"]),
                               np.stack(ref.history["trust"]), atol=1e-4)
    np.testing.assert_array_equal(np.stack(srv.history["selected"]),
                                  np.stack(ref.history["selected"]))


def test_mesh_requires_divisible_fleet():
    fed = fleet_fed(12, mesh_shape=SHARDS)  # 12 % 8 != 0
    with pytest.raises(ValueError, match="divisible"):
        FedAREngine(small_model(32), fed, TaskRequirement())


def test_sharded_emnist_pipeline_N512_matches_single_device():
    """Acceptance bar for the dataset subsystem: an N=512 run on the
    EMNIST-or-fallback pipeline (ragged label-skew shards, masked padding),
    sharded 8 ways, matches the single-device engine within fp32 tolerance —
    with no network access (CI has a cold cache, so this exercises the
    deterministic offline fallback)."""
    from repro.data.datasets import make_federated

    n = 512
    ds = make_federated(
        "emnist", n, scenario="label_skew", samples_per_client=24, seed=3
    )
    assert ds.mask is not None  # ragged shards ride the masked path
    data = {k: jnp.asarray(v) for k, v in ds.arrays().items()}
    e1, e8 = _engines("fedar", n=n)
    _assert_equivalent(e1, e8, data)


def test_sharded_packed_gated_matches_single_device():
    """The padding-free hot path on the mesh: bucketed shard-major packing
    + selection-gated SGD, 8 client shards vs 1 device, fp32 parity.  The
    two engines consume DIFFERENT physical layouts (shards=1 vs shards=8
    packings of the same dataset) — the numerics must not notice."""
    from repro.data.datasets import make_federated

    n = 64
    ds = make_federated(
        "digits", n, scenario="quantity_skew", samples_per_client=24, seed=9
    )
    for frac in (None, 0.5):
        kw = dict(local_epochs=1, defense="foolsgold_sketch",
                  select_frac=frac)
        e1 = FedAREngine(small_model(32), fleet_fed(n, **kw),
                         TaskRequirement())
        e8 = FedAREngine(small_model(32),
                         fleet_fed(n, mesh_shape=SHARDS, **kw),
                         TaskRequirement())
        d1 = jax.tree.map(jnp.asarray, ds.packed_arrays(shards=1,
                                                        quantum=20))
        d8 = jax.tree.map(jnp.asarray, ds.packed_arrays(shards=SHARDS,
                                                        quantum=20))
        s1, o1 = e1.run(e1.init_state(), d1, rounds=ROUNDS)
        s8, o8 = e8.run(e8.init_state(), d8, rounds=ROUNDS)
        np.testing.assert_array_equal(np.asarray(o1.selected),
                                      np.asarray(o8.selected))
        np.testing.assert_allclose(np.asarray(o1.trust),
                                   np.asarray(o8.trust), atol=1e-4)
        np.testing.assert_allclose(np.asarray(s1.params),
                                   np.asarray(s8.params), atol=1e-4,
                                   rtol=1e-4)


def test_sharded_packed_gated_matches_dense_gated():
    """The two-pass global cohort under sharding: selection is counted
    globally and ONE capped gather builds the cohort, so packed+gated on
    the 8-way mesh must land on the dense gated trajectory of the SAME
    fleet — selection masks exact, params to fp32 psum tolerance."""
    from repro.data.datasets import make_federated

    n = 64
    ds = make_federated(
        "digits", n, scenario="quantity_skew", samples_per_client=24,
        seed=11,
    )

    def run(layout, frac):
        kw = dict(local_epochs=1, defense="foolsgold_sketch",
                  select_frac=frac, mesh_shape=SHARDS)
        e = FedAREngine(small_model(32), fleet_fed(n, **kw),
                        TaskRequirement())
        data = jax.tree.map(
            jnp.asarray,
            ds.engine_arrays(shards=SHARDS, quantum=20, layout=layout),
        )
        return e.run(e.init_state(), data, rounds=ROUNDS)

    s_d, o_d = run("dense", 0.5)
    s_p, o_p = run("packed", 0.5)
    np.testing.assert_array_equal(np.asarray(o_d.selected),
                                  np.asarray(o_p.selected))
    np.testing.assert_array_equal(np.asarray(o_d.on_time),
                                  np.asarray(o_p.on_time))
    np.testing.assert_allclose(np.asarray(o_d.trust), np.asarray(o_p.trust),
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(s_d.params),
                               np.asarray(s_p.params), atol=1e-4, rtol=1e-4)


def test_sharded_padded_fleet_via_prepare_data():
    """A 60-robot fleet on an 8-way mesh: ``padded_to`` fills it to 64 with
    inert dummies and ``prepare_data`` (auto layout) feeds both engines;
    the mesh run matches the single-device engine on the padded fleet."""
    from repro.data.datasets import make_federated

    ds = make_federated(
        "digits", 60, scenario="quantity_skew", samples_per_client=24,
        seed=13,
    ).padded_to(SHARDS)
    assert ds.num_clients == 64
    assert ds.meta["padded_clients"] == 4
    kw = dict(local_epochs=1, defense="foolsgold_sketch")
    e1 = FedAREngine(small_model(32), fleet_fed(64, **kw),
                     TaskRequirement())
    e8 = FedAREngine(small_model(32),
                     fleet_fed(64, mesh_shape=SHARDS, **kw),
                     TaskRequirement())
    s1, o1 = e1.run(e1.init_state(), e1.prepare_data(ds), rounds=ROUNDS)
    s8, o8 = e8.run(e8.init_state(), e8.prepare_data(ds), rounds=ROUNDS)
    np.testing.assert_array_equal(np.asarray(o1.selected),
                                  np.asarray(o8.selected))
    np.testing.assert_allclose(np.asarray(s1.params),
                               np.asarray(s8.params), atol=1e-4, rtol=1e-4)


def test_sharded_robot_drift_schedule_matches_single_device():
    """The drift schedule's (W, N, n) round_mask shards its CLIENT axis
    (axis 1); the windowed round loop must reproduce the single-device
    engine across shards."""
    from repro.data.datasets import make_federated

    n = 64
    ds = make_federated(
        "emnist", n, scenario="robot_drift", samples_per_client=48,
        windows=3, seed=5,
    )
    assert ds.round_mask is not None and ds.round_mask.shape[0] == 3
    data = {k: jnp.asarray(v) for k, v in ds.arrays().items()}
    e1, e8 = _engines("fedar", n=n)
    _assert_equivalent(e1, e8, data)
