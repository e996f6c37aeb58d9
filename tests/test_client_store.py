"""Host-store cohort engine: the inert-dummy contract, the numpy client
store, host-side cohort sampling, the two-level tree reduce, and the
CohortEngine/FedARServer integration (K >= N reduces to the resident
path exactly; device input shapes are independent of N).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.fedar_mnist import fleet_fed, small_model
from repro.core.client_store import ClientStore
from repro.core.engine import CohortEngine
from repro.core.fedar import FedARServer
from repro.core.resources import TaskRequirement
from repro.core.selection import sample_cohort
from repro.core.trust import TrustState
from repro.data.datasets import VirtualFleet, inert_clients, make_federated

REQ = TaskRequirement()


def _cohort_fed(n, k, **kw):
    kw.setdefault("local_epochs", 1)
    kw.setdefault("defense", "foolsgold_sketch")
    kw.setdefault("defense_sketch_dim", 32)
    return fleet_fed(n, cohort_size=k, **kw)


# ------------------------------------------------------- inert contract
def test_inert_clients_contract():
    blank = inert_clients(3, 7, 5, windows=2)
    assert not blank["mask"].any()
    assert not blank["round_mask"].any()
    assert (blank["sizes"] == 0).all()
    assert blank["x"].shape == (3, 7, 5)
    assert blank["round_mask"].shape == (2, 3, 7)


def test_padded_to_pads_with_inert_clients():
    ds = make_federated("table2", 12, samples_per_client=40).padded_to(8)
    assert ds.num_clients == 16
    assert (ds.sizes[12:] == 0).all()
    assert not ds.mask[12:].any()
    assert ds.mask[:12].all()  # real clients stay dense


def test_cohort_underfill_is_inert_regardless_of_source_row():
    """Underfill slots must be bit-identical no matter which client row
    the (masked-out) index happens to point at — the engine only ever
    sees the inert_clients contract."""
    ds = make_federated("table2", 12, samples_per_client=40)
    valid = np.array([True, True, False, False])
    a = ds.cohort_arrays(np.array([0, 5, 1, 2]), valid)
    b = ds.cohort_arrays(np.array([0, 5, 9, 11]), valid)
    for key in a:
        np.testing.assert_array_equal(np.asarray(a[key]), np.asarray(b[key]),
                                      err_msg=key)
    assert (np.asarray(a["sizes"])[2:] == 0).all()
    assert not np.asarray(a["mask"])[2:].any()


# ---------------------------------------------------------- ClientStore
def test_store_gather_scatter_roundtrip():
    store = ClientStore(_cohort_fed(32, 8), history_dim=4)
    idx = np.array([1, 5, 9, 30])
    valid = np.array([True, True, True, False])
    rows = store.gather(idx)
    assert rows["score"].shape == (4,)
    assert rows["history"].shape == (4, 4)
    trust = TrustState(
        rows["score"] + 8.0,
        rows["participations"] + 1,
        rows["failures"],
    )
    battery = rows["battery"] - 0.02
    history = rows["history"] + 1.0
    store.scatter_round(idx, valid, trust=trust, battery=battery,
                        history=history)
    np.testing.assert_allclose(store.score[[1, 5, 9]], 58.0)
    np.testing.assert_allclose(store.history[1], 1.0)
    # the invalid slot's client is untouched
    assert store.score[30] == 50.0
    assert (store.history[30] == 0).all()


def test_store_finish_round_interest_and_trickle():
    fed = _cohort_fed(16, 4)
    store = ClientStore(fed, history_dim=0)
    b0 = store.battery.copy()
    idx = np.array([0, 1, 2, 3])
    valid = np.ones(4, bool)
    eligible = np.ones(16, bool)
    store.finish_round(idx, valid, eligible)
    # eligible non-cohort clients earn c_interested; cohort members don't
    np.testing.assert_allclose(store.score[4:], 50.0 + fed.c_interested)
    np.testing.assert_allclose(store.score[:4], 50.0)
    # idle battery trickle, capped at 1
    np.testing.assert_allclose(
        store.battery[4:], np.minimum(b0[4:] + 0.005, 1.0), atol=1e-7
    )
    assert (store.last_selected[:4] == 0).all()
    assert (store.last_selected[4:] == -1).all()
    assert int(store.round_idx) == 1


def test_store_finish_round_all_ineligible():
    """An all-ineligible round (dead/banned fleet): no interest credit
    lands anywhere, everyone trickle-charges, the round counter advances
    and nothing is stamped as selected."""
    fed = _cohort_fed(16, 4)
    store = ClientStore(fed, history_dim=0)
    s0 = store.score.copy()
    b0 = store.battery.copy()
    store.finish_round(np.zeros(4, np.int64), np.zeros(4, bool),
                       np.zeros(16, bool))
    np.testing.assert_array_equal(store.score, s0)
    np.testing.assert_allclose(
        store.battery, np.minimum(b0 + 0.005, 1.0), atol=1e-7
    )
    assert (store.last_selected == -1).all()
    assert int(store.round_idx) == 1


def test_store_finish_round_all_dummy_cohort_keeps_interest():
    """A fully-underfilled cohort with eligible clients (can happen when
    eligibility changed between sampling and settlement): every eligible
    client earns C_Interested — nobody was actually in the cohort."""
    fed = _cohort_fed(16, 4)
    store = ClientStore(fed, history_dim=0)
    eligible = np.zeros(16, bool)
    eligible[[2, 7]] = True
    store.finish_round(np.array([2, 7, 0, 0]), np.zeros(4, bool), eligible)
    np.testing.assert_allclose(store.score[[2, 7]], 50.0 + fed.c_interested)
    np.testing.assert_allclose(store.score[[0, 1, 3]], 50.0)
    assert (store.last_selected == -1).all()


def test_store_blocks_are_zero_copy_shards():
    store = ClientStore(_cohort_fed(32, 8), history_dim=2, num_shards=4)
    blk = store.block(1)
    assert blk["score"].shape == (8,)
    assert np.shares_memory(blk["score"], store.score)
    with pytest.raises(IndexError):
        store.block(4)
    with pytest.raises(ValueError, match="divide"):
        ClientStore(_cohort_fed(30, 8), history_dim=0, num_shards=4)


def test_store_state_dict_roundtrip_via_ckpt(tmp_path):
    from repro.checkpoint import ckpt

    fed = _cohort_fed(16, 4)
    store = ClientStore(fed, history_dim=3)
    store.score[:] = np.arange(16)
    store.history[:] = 7.0
    store.finish_round(np.array([0, 1, 2, 3]), np.ones(4, bool),
                       np.ones(16, bool))
    params = np.linspace(0, 1, 10).astype(np.float32)
    path = str(tmp_path / "store.ckpt")
    ckpt.save_store(path, store, params=params, step=1)

    fresh = ClientStore(fed, history_dim=3)
    got, step = ckpt.restore_store(path, fresh, with_params=True)
    assert step == 1
    np.testing.assert_array_equal(np.asarray(got), params)
    for name, arr in store.state_dict().items():
        np.testing.assert_array_equal(
            np.asarray(fresh.state_dict()[name]), arr, err_msg=name
        )

    # params are optional on save, so demanding them must fail loudly
    bare = str(tmp_path / "bare.ckpt")
    ckpt.save_store(bare, store)
    with pytest.raises(ValueError, match="no bundled params"):
        ckpt.restore_store(bare, fresh, with_params=True)

    # and a store of the wrong fleet size is a shape mismatch
    with pytest.raises(ValueError):
        ckpt.restore_store(path, ClientStore(_cohort_fed(32, 4), 3))


# -------------------------------------------------------- sample_cohort
def test_sample_cohort_deterministic_and_round_keyed():
    fed = _cohort_fed(64, 8)
    store = ClientStore(fed, history_dim=0)
    kw = dict(cohort_size=8, round_idx=0)
    a = sample_cohort(store.score, store.resources_view(), REQ, fed, **kw)
    b = sample_cohort(store.score, store.resources_view(), REQ, fed, **kw)
    np.testing.assert_array_equal(a[0], b[0])
    c = sample_cohort(store.score, store.resources_view(), REQ, fed,
                      cohort_size=8, round_idx=1)
    assert not np.array_equal(a[0], c[0])
    assert a[1].all() and np.array_equal(a[0], np.sort(a[0]))


def test_sample_cohort_prefers_trust():
    fed = _cohort_fed(64, 8, client_fraction=0.25)
    store = ClientStore(fed, history_dim=0)
    store.score[:16] = 99.0  # pool = top 16 by trust -> exactly these
    idx, valid, ok = sample_cohort(
        store.score, store.resources_view(), REQ, fed,
        cohort_size=8, round_idx=0,
    )
    assert valid.all()
    assert (idx < 16).all()


def test_sample_cohort_underfills_when_few_eligible():
    fed = _cohort_fed(32, 8)
    store = ClientStore(fed, history_dim=0)
    store.battery[:] = 0.0
    store.battery[[3, 17, 29]] = 1.0
    idx, valid, ok = sample_cohort(
        store.score, store.resources_view(), REQ, fed,
        cohort_size=8, round_idx=0,
    )
    assert valid.sum() == 3
    np.testing.assert_array_equal(idx[valid], [3, 17, 29])
    assert ok.sum() == 3
    # nobody eligible -> fully inert round, no crash
    store.battery[:] = 0.0
    idx, valid, ok = sample_cohort(
        store.score, store.resources_view(), REQ, fed,
        cohort_size=8, round_idx=0,
    )
    assert not valid.any() and not ok.any()


# -------------------------------------------- engine integration (K < N)
def test_cohort_engine_validates_config():
    model = small_model(16)
    with pytest.raises(ValueError, match="resident"):
        CohortEngine(model, _cohort_fed(16, 16), REQ)
    with pytest.raises(ValueError, match="buffer"):
        CohortEngine(model, _cohort_fed(32, 8, aggregation="async_seq"), REQ)
    with pytest.raises(ValueError, match="select_frac"):
        CohortEngine(model, _cohort_fed(32, 8, select_frac=0.5), REQ)
    with pytest.raises(ValueError, match="cohort-"):
        CohortEngine(model, _cohort_fed(32, 8, defense="foolsgold"), REQ)


def test_cohort_run_smoke_and_history_layout():
    n, k, rounds = 48, 8, 3
    fleet = VirtualFleet(n, samples_per_client=40, seed=0)
    srv = FedARServer(small_model(16), _cohort_fed(n, k), REQ)
    assert srv.cohort_mode
    hist = srv.run(fleet, rounds)
    assert len(hist["cohort"]) == rounds
    for idx, valid in hist["cohort"]:
        assert idx.shape == (k,) and valid.shape == (k,)
    assert srv.round_idx == rounds
    # trust/battery evolved on the host store
    score = np.asarray(srv.trust.score)
    assert (score != 50.0).any()
    assert np.isfinite(np.asarray(srv.engine.params)).all()
    # the trust table is fleet-sized even though devices only saw K rows
    assert score.shape == (n,)


def test_cohort_matches_resident_when_k_equals_n():
    """cohort_size >= N strips to the resident engine — bit-identical
    histories and parameters, no cohort bookkeeping."""
    n, rounds = 24, 3
    fleet = VirtualFleet(n, samples_per_client=40, seed=0)
    ref = FedARServer(small_model(16), _cohort_fed(n, None), REQ)
    ha = ref.run(ref.engine.prepare_data(fleet.materialize()), rounds)
    srv = FedARServer(small_model(16), _cohort_fed(n, n), REQ)
    hb = srv.run(fleet, rounds)  # fleet object -> materialized internally
    assert not srv.cohort_mode and "cohort" not in hb
    np.testing.assert_array_equal(
        np.asarray(ref.state.params), np.asarray(srv.state.params)
    )
    for x, y in zip(ha["trust"], hb["trust"]):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    for x, y in zip(ha["selected"], hb["selected"]):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_cohort_all_dummy_round_is_inert():
    """A round where nobody in the fleet is eligible must not crash, must
    leave the global model bitwise untouched, and must keep the host
    bookkeeping consistent (round advances, scores frozen)."""
    n, k = 32, 8
    eng = CohortEngine(small_model(16), _cohort_fed(n, k), REQ)
    fleet = VirtualFleet(n, samples_per_client=40, seed=0)
    eng.store.battery[:] = 0.0  # dead fleet -> sample_cohort underfills to 0
    p0 = np.asarray(eng.params).copy()
    s0 = eng.store.score.copy()
    idx, valid, out = eng.run_round(fleet)
    assert not valid.any()
    np.testing.assert_array_equal(np.asarray(eng.params), p0)
    np.testing.assert_array_equal(eng.store.score, s0)
    assert int(eng.store.round_idx) == 1
    assert (eng.store.last_selected == -1).all()


# ------------------------------------------------- store-resident async
def test_cohort_async_pending_lives_in_the_store():
    """aggregation='async' in cohort mode: the in-flight delta buffer is a
    store column that follows clients on and off the device.  A
    sub-latency timeout forces every upload to lag >= 1 round, so slots
    must be in flight in the host table between rounds."""
    n, k = 48, 8
    eng = CohortEngine(
        small_model(16), _cohort_fed(n, k, aggregation="async",
                                     timeout=1e-3), REQ)
    assert eng.store.pending_dim == eng.dim
    fleet = VirtualFleet(n, samples_per_client=40, seed=0)
    eng.run(fleet, rounds=3)
    live = eng.store.pending_valid
    assert live.any()
    assert np.abs(eng.store.pending_delta[live]).sum() > 0
    # issue/arrival tags are absolute rounds; a lagged upload arrives later
    assert (eng.store.pending_arrival[live]
            > eng.store.pending_issued[live]).all()
    assert np.isfinite(np.asarray(eng.params)).all()


def test_cohort_async_k_geq_n_reduces_to_resident():
    """cohort_size >= N with aggregation='async' strips to the resident
    buffered-async engine bit-identically (the former ValueError is gone)."""
    n, rounds = 24, 3
    fleet = VirtualFleet(n, samples_per_client=40, seed=0)
    ref = FedARServer(
        small_model(16), _cohort_fed(n, None, aggregation="async"), REQ)
    ha = ref.run(ref.engine.prepare_data(fleet.materialize()), rounds)
    srv = FedARServer(
        small_model(16), _cohort_fed(n, n, aggregation="async"), REQ)
    hb = srv.run(fleet, rounds)
    assert not srv.cohort_mode and "cohort" not in hb
    np.testing.assert_array_equal(
        np.asarray(ref.state.params), np.asarray(srv.state.params)
    )
    for x, y in zip(ha["trust"], hb["trust"]):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_cohort_device_inputs_independent_of_fleet_size():
    """The jit-boundary pytree is shaped by K alone: growing the fleet
    16x must not change a single device-input shape."""
    k = 8
    shapes = []
    for n in (4096, 65536):
        eng = CohortEngine(small_model(16), _cohort_fed(n, k), REQ)
        fleet = VirtualFleet(n, samples_per_client=40, seed=0)
        state, data, idx, valid, elig = eng._build_round_inputs(fleet)
        shapes.append(jax.tree.map(jnp.shape, (state, data)))
        assert idx.shape == (k,) and elig.shape == (n,)
    assert shapes[0] == shapes[1]


@pytest.mark.skipif(jax.device_count() < 8, reason="needs 8 devices")
def test_reduce_tree_matches_flat_psum():
    from jax.sharding import PartitionSpec as P

    from repro.core.distributed import MeshComms, client_mesh

    fed = fleet_fed(64, mesh_shape=8)
    mesh = client_mesh(fed)
    flat_c = MeshComms("clients", 8, tree=False)
    tree_c = MeshComms("clients", 8, tree=True)
    x = jnp.asarray(np.random.default_rng(0).normal(size=(8, 37)), jnp.float32)

    def run(comms):
        def body(xb):  # (1, 37) shard block -> contribute its one row
            return comms.reduce_tree(xb[0])

        f = jax.shard_map(body, mesh=mesh, in_specs=P("clients"),
                          out_specs=P(), check_vma=False)
        return f(x)

    np.testing.assert_array_equal(np.asarray(run(flat_c)),
                                  np.asarray(run(tree_c)))
    np.testing.assert_allclose(
        np.asarray(run(tree_c)), np.asarray(x.sum(0)), rtol=1e-6
    )


@pytest.mark.skipif(jax.device_count() < 8, reason="needs 8 devices")
def test_cohort_mesh_matches_single_device():
    n, k, rounds = 64, 16, 3
    fleet = VirtualFleet(n, samples_per_client=40, seed=0)
    a = FedARServer(small_model(16), _cohort_fed(n, k), REQ)
    ha = a.run(fleet, rounds)
    b = FedARServer(small_model(16), _cohort_fed(n, k, mesh_shape=8), REQ)
    hb = b.run(fleet, rounds)
    # host-side sampling is device-count independent: identical cohorts
    for x, y in zip(ha["cohort"], hb["cohort"]):
        np.testing.assert_array_equal(x[0], y[0])
    np.testing.assert_allclose(
        np.asarray(a.engine.params), np.asarray(b.engine.params), atol=1e-5
    )
    np.testing.assert_array_equal(
        np.asarray(a.trust.score), np.asarray(b.trust.score)
    )
