"""The padding-free hot path: bucketed client packing + selection-gated
local SGD must be NUMERICALLY INVISIBLE — bit-identical (fp32) engine
trajectories against the pad-to-max rectangular layout and the full-N vmap.

Layout laws are unit-tested (bucket widths, perm/inv round trip, shard-
major layout, the <= 2x waste bound); the end-to-end bit-identity is a
hypothesis property over every registered scenario.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.configs.fedar_mnist import fleet_fed, small_model
from repro.core.engine import FedAREngine
from repro.core.resources import TaskRequirement
from repro.data.datasets import make_federated
from repro.data.scenarios import padding_waste

SCENARIO_NAMES = ("iid", "label_skew", "quantity_skew", "robot_drift")


def _engine(n, **kw):
    kw.setdefault("local_epochs", 2)
    return FedAREngine(small_model(8), fleet_fed(n, **kw), TaskRequirement())


def _run(engine, data, rounds=3):
    state, outs = engine.run(
        engine.init_state(), jax.tree.map(jnp.asarray, data), rounds=rounds
    )
    return state, outs


def _assert_states_equal(s0, s1):
    np.testing.assert_array_equal(np.asarray(s0.params),
                                  np.asarray(s1.params))
    np.testing.assert_array_equal(np.asarray(s0.trust.score),
                                  np.asarray(s1.trust.score))
    np.testing.assert_array_equal(np.asarray(s0.fg_history),
                                  np.asarray(s1.fg_history))
    np.testing.assert_array_equal(np.asarray(s0.resources.battery),
                                  np.asarray(s1.resources.battery))


def _assert_states_close(s0, s1, tol=1e-5):
    """Gated-path comparison: deviation/aggregation consume the compact
    cohort (known-zero rows skipped), which shifts fp32 summation order by
    ulps — every selected client's delta and all integer bookkeeping stay
    exact, the reductions agree to tight fp32 tolerance."""
    np.testing.assert_allclose(np.asarray(s0.params),
                               np.asarray(s1.params), rtol=tol, atol=tol)
    np.testing.assert_array_equal(np.asarray(s0.trust.score),
                                  np.asarray(s1.trust.score))
    np.testing.assert_allclose(np.asarray(s0.fg_history),
                               np.asarray(s1.fg_history), rtol=tol, atol=tol)
    np.testing.assert_array_equal(np.asarray(s0.resources.battery),
                                  np.asarray(s1.resources.battery))


# ---------------------------------------------------------------- layout

def test_packed_layout_laws():
    ds = make_federated("digits", 16, scenario="quantity_skew",
                        samples_per_client=30, seed=1)
    pk = ds.packed_arrays()["packed"]
    n_max = ds.samples
    extent = ds.client_extents()
    rows_total = 0
    seen = np.zeros(16, bool)
    for xb, perm, valid, mb in zip(pk["x"], pk["perm"], pk["valid"],
                                   pk["mask"]):
        L = xb.shape[1]
        assert L <= n_max
        assert L & (L - 1) == 0 or L == n_max  # pow2, or capped at n_max
        for r in range(xb.shape[0]):
            if valid[r]:
                cid = int(perm[r])
                assert not seen[cid]
                seen[cid] = True
                assert extent[cid] <= L  # no real sample truncated
                np.testing.assert_array_equal(xb[r], ds.x[cid, :L])
                np.testing.assert_array_equal(mb[r], ds.mask[cid, :L])
            else:
                assert not mb[r].any()  # dummy rows never train
        rows_total += xb.shape[0]
    assert seen.all()
    # inverse permutation round trip: inv[c] indexes the concat of buckets
    cat_perm = np.concatenate(pk["perm"])
    cat_valid = np.concatenate(pk["valid"])
    inv = pk["inv"]
    for c in range(16):
        assert cat_valid[inv[c]] and cat_perm[inv[c]] == c


def test_packed_waste_bound():
    """Pad-to-bucket padded volume stays within 2x of the real samples
    (modulo the min_width floor), vs the ~n_max/mean blow-up of pad-to-max."""
    ds = make_federated("digits", 64, scenario="quantity_skew",
                        samples_per_client=50, seed=3, alpha=0.3)
    pk = ds.packed_arrays(min_width=1)["packed"]
    padded = sum(x.shape[0] * x.shape[1] for x in pk["x"])
    real = int(ds.sizes.sum())
    assert padded <= 2 * real
    waste = padding_waste(ds.sizes.astype(int))
    assert waste["bucketed"] <= 2.0 < waste["pad_to_max"]


def test_packed_shard_major_layout():
    """With shards=k each bucket's rows split into k equal shard segments
    holding only that shard block's clients (local perm indices)."""
    ds = make_federated("digits", 16, scenario="quantity_skew",
                        samples_per_client=30, seed=1)
    pk = ds.packed_arrays(shards=4)["packed"]
    assert int(pk["shards"]) == 4
    for perm, valid in zip(pk["perm"], pk["valid"]):
        rows = perm.shape[0]
        assert rows % 4 == 0
        cap = rows // 4
        for s in range(4):
            seg_perm = perm[s * cap: (s + 1) * cap]
            seg_valid = valid[s * cap: (s + 1) * cap]
            assert (seg_perm[seg_valid] < 4).all()  # local block indices


def test_packed_quantum_widths_are_batch_pow2():
    ds = make_federated("digits", 32, scenario="quantity_skew",
                        samples_per_client=40, seed=2)
    pk = ds.packed_arrays(quantum=20)["packed"]
    for xb in pk["x"]:
        L = xb.shape[1]
        nb = -(-L // 20)
        assert L == ds.samples or (L % 20 == 0 and nb & (nb - 1) == 0)


def test_packed_shards_pad_non_divisible():
    """A fleet that doesn't divide by ``shards`` no longer raises: it is
    padded with inert dummy clients (``padded_to``) and the returned dict
    describes the padded fleet."""
    ds = make_federated("digits", 16, scenario="iid", samples_per_client=20)
    out = ds.packed_arrays(shards=3)
    assert out["sizes"].shape == (18,)
    np.testing.assert_array_equal(out["sizes"][16:], 0.0)
    pk = out["packed"]
    assert int(pk["shards"]) == 3
    total_valid = 0
    for xb, valid in zip(pk["x"], pk["valid"]):
        assert xb.shape[0] % 3 == 0  # shard-major rows still equalized
        total_valid += int(valid.sum())
    assert total_valid == 18  # dummies are real (inert) rows, not invalid
    assert pk["inv"].shape == (18,)


def test_padded_to_inert_dummies():
    """``padded_to`` appends clients that can never train or weigh into
    aggregation: all-False sample mask, exactly-zero sizes, zero-padded
    drift schedule; a divisible fleet is returned unchanged."""
    ds = make_federated("digits", 10, scenario="robot_drift",
                        samples_per_client=24, seed=7)
    assert ds.padded_to(5) is ds
    pds = ds.padded_to(4)
    assert pds.num_clients == 12
    assert pds.meta["real_clients"] == 10 and pds.meta["padded_clients"] == 2
    assert not pds.mask[10:].any()
    np.testing.assert_array_equal(pds.sizes[10:], 0.0)
    assert pds.round_mask.shape == (ds.windows, 12, ds.samples)
    assert not pds.round_mask[:, 10:].any()
    # real clients untouched
    np.testing.assert_array_equal(pds.x[:10], ds.x)
    np.testing.assert_array_equal(pds.sizes[:10], ds.sizes)
    # extents: an all-False-mask dummy packs into the narrowest bucket
    assert (pds.client_extents()[10:] == 1).all()


def test_padded_fleet_packed_bit_identical():
    """Dummy clients ride the packed + fused paths exactly like the dense
    rectangle: all-False masks mean zero delta, zero sizes mean zero
    aggregation weight, and the trajectories stay bit-equal."""
    ds = make_federated("digits", 16, scenario="quantity_skew",
                        samples_per_client=30, seed=5).padded_to(5)
    assert ds.num_clients == 20
    engine = _engine(20)
    s0, _ = _run(engine, ds.arrays())
    s1, _ = _run(engine, ds.packed_arrays())
    _assert_states_equal(s0, s1)


# ----------------------------------------------------- engine bit-identity

@pytest.mark.parametrize("scenario", SCENARIO_NAMES)
def test_packed_engine_bit_identical(scenario):
    """Acceptance bar: the bucketed packed pipeline reproduces the
    pad-to-max engine trajectory BIT-EXACTLY (fp32) on every scenario."""
    ds = make_federated("digits", 16, scenario=scenario,
                        samples_per_client=30, seed=2)
    engine = _engine(16, defense="foolsgold_sketch")
    s0, o0 = _run(engine, ds.arrays())
    s1, o1 = _run(engine, ds.packed_arrays())
    _assert_states_equal(s0, s1)
    np.testing.assert_array_equal(np.asarray(o0.selected),
                                  np.asarray(o1.selected))
    np.testing.assert_array_equal(np.asarray(o0.on_time),
                                  np.asarray(o1.on_time))


@settings(max_examples=4, deadline=None)
@given(
    scenario=st.sampled_from(SCENARIO_NAMES),
    seed=st.integers(0, 50),
    samples=st.integers(8, 40),
    quantum=st.sampled_from([None, 20]),
)
def test_packed_engine_bit_identical_property(scenario, seed, samples,
                                              quantum):
    """Hypothesis sweep of the same law over seeds / sample budgets /
    bucket quantization."""
    ds = make_federated("digits", 8, scenario=scenario,
                        samples_per_client=samples, seed=seed)
    engine = _engine(8, local_epochs=1)
    s0, _ = _run(engine, ds.arrays(), rounds=2)
    s1, _ = _run(engine, ds.packed_arrays(quantum=quantum), rounds=2)
    _assert_states_equal(s0, s1)


def test_packed_lone_row_bucket_bit_identical():
    """A bucket holding a single client (here the 21-sample client of
    quantity_skew seed 16) trains bit-identically to the same client inside
    the dense block: XLA lowers a size-1 vmap without its batch axis, which
    reorders the gradient sums, so the engine runs a lone row batched."""
    ds = make_federated("digits", 8, scenario="quantity_skew",
                        samples_per_client=8, seed=16)
    packed = ds.packed_arrays(quantum=None)
    assert min(x.shape[0] for x in packed["packed"]["x"]) == 1
    engine = _engine(8, local_epochs=1)
    s0, _ = _run(engine, ds.arrays(), rounds=2)
    s1, _ = _run(engine, packed, rounds=2)
    _assert_states_equal(s0, s1)


# ------------------------------------------------------- selection gating

@pytest.mark.parametrize("frac", [0.5, 1.0])
def test_gated_equals_full_vmap_dense(frac):
    """Selection-gated SGD == the full-N vmap on the dense fleet: the gated
    cohort covers every selected client and unselected deltas are exact
    zeros, so the trajectory is unchanged."""
    from repro.data.federated import scaled_fleet

    data = scaled_fleet(32, samples_per_client=40)
    s0, o0 = _run(_engine(32, local_epochs=1), data)
    s1, o1 = _run(_engine(32, local_epochs=1, select_frac=frac), data)
    _assert_states_close(s0, s1)
    np.testing.assert_array_equal(np.asarray(o0.selected),
                                  np.asarray(o1.selected))


@pytest.mark.parametrize("aggregation",
                         ["fedar", "fedavg", "async", "async_seq"])
def test_gated_equals_full_vmap_across_modes(aggregation):
    """Every aggregation mode — including async_seq, which folds the raw
    LOCAL MODELS rather than deltas — sees identical numerics through the
    gated path (unselected clients' local params equal the global)."""
    from repro.data.federated import scaled_fleet

    data = scaled_fleet(16, samples_per_client=40)
    kw = dict(local_epochs=1, aggregation=aggregation)
    s0, _ = _run(_engine(16, **kw), data)
    s1, _ = _run(_engine(16, select_frac=0.5, **kw), data)
    _assert_states_close(s0, s1)


def test_packed_engine_async_seq_bit_identical():
    """async_seq on the packed layout: the legacy sequential fold consumes
    locals_flat, which the packed path reconstructs exactly."""
    ds = make_federated("digits", 16, scenario="quantity_skew",
                        samples_per_client=30, seed=2)
    kw = dict(local_epochs=1, aggregation="async_seq")
    s0, _ = _run(_engine(16, **kw), ds.arrays())
    s1, _ = _run(_engine(16, **kw), ds.packed_arrays())
    _assert_states_equal(s0, s1)


def test_gated_packed_equals_dense_full():
    """Gating composed with bucketed packing still lands on the pad-to-max
    full-vmap trajectory bit-exactly."""
    ds = make_federated("digits", 16, scenario="quantity_skew",
                        samples_per_client=30, seed=4)
    s0, _ = _run(_engine(16), ds.arrays())
    s1, _ = _run(_engine(16, select_frac=0.5), ds.packed_arrays(quantum=20))
    _assert_states_close(s0, s1)


def test_engine_sgd_kernel_routing_matches_xla():
    """sgd_impl="kernel" through the ENGINE (interpret mode off-TPU) must
    match the XLA vmap path — pins the engine glue the kernel tests can't
    see: the dense block as the ragged kernel's one-bucket case, the
    all-ones mask fallback for dense fleets, and the b1/b2/w1/w2 concat
    order that must track flatten()'s sorted-leaf order."""
    from repro.data.federated import scaled_fleet

    data = scaled_fleet(6, samples_per_client=40)
    s0, _ = _run(_engine(6, local_epochs=1), data, rounds=2)
    s1, _ = _run(_engine(6, local_epochs=1, sgd_impl="kernel"), data,
                 rounds=2)
    np.testing.assert_allclose(np.asarray(s0.params), np.asarray(s1.params),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(s0.trust.score),
                                  np.asarray(s1.trust.score))
    # masked path too: ragged packed buckets through the fused kernel
    ds = make_federated("digits", 6, scenario="quantity_skew",
                        samples_per_client=20, seed=3)
    s0, _ = _run(_engine(6, local_epochs=1), ds.packed_arrays(), rounds=2)
    s1, _ = _run(_engine(6, local_epochs=1, sgd_impl="kernel"),
                 ds.packed_arrays(), rounds=2)
    np.testing.assert_allclose(np.asarray(s0.params), np.asarray(s1.params),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("impl,layout,route", [
    ("einsum", "dense", "xla"),
    ("einsum", "packed", "xla"),
    ("kernel", "dense", "fused_ragged"),
    ("kernel", "packed", "fused_ragged"),
])
def test_sgd_route_recorded(impl, layout, route):
    """The local-SGD route is decided host-side from the config, recorded
    on the engine (and in ``kernel_routes``) before tracing, and is the
    route the round takes on either layout."""
    ds = make_federated("digits", 6, scenario="quantity_skew",
                        samples_per_client=20, seed=3)
    data = ds.arrays() if layout == "dense" else ds.packed_arrays()
    engine = _engine(6, local_epochs=1, sgd_impl=impl)
    assert engine.sgd_route == route
    engine.step(engine.init_state(), jax.tree.map(jnp.asarray, data))
    assert engine.sgd_route == route
    assert engine.kernel_routes()["sgd"] == route


def test_sgd_route_auto_follows_backend():
    """``auto`` takes the kernels only where they compile (a TPU backend),
    the XLA path everywhere else."""
    from repro.data.federated import scaled_fleet
    from repro.kernels.ops import interpret_mode

    engine = _engine(6, local_epochs=1)
    data = scaled_fleet(6, samples_per_client=20)
    engine.step(engine.init_state(), jax.tree.map(jnp.asarray, data))
    routes = engine.kernel_routes()
    if interpret_mode():
        assert routes["sgd"] == "xla"
        assert set(routes.values()) <= {"xla", "einsum", "none"}
    else:
        assert routes["sgd"] == "fused_ragged" and routes["agg"] == "kernel"


def test_explicit_kernel_too_wide_raises():
    """A client block of any width takes the kernel — its tiles stream one
    at a time, so a 4,000-sample client needs no more VMEM than a 20-sample
    one — but an explicit ``sgd_impl="kernel"`` whose batch tile is wider
    than the fused kernel's compiled VMEM limit admits raises when the
    engine is built, before any tracing, instead of silently running the
    XLA path."""
    from repro.kernels.local_sgd import fused_fits_vmem

    width = 4000
    engine = _engine(2, local_epochs=1, sgd_impl="kernel")
    data = {"x": np.zeros((2, width, 784), np.float32),
            "y": np.zeros((2, width), np.int32),
            "activations": np.zeros(2, np.int32)}
    engine._check_packed(data)  # all a step checks of the data
    assert engine.kernel_routes()["sgd"] == "fused_ragged"
    batch = 8192
    assert not fused_fits_vmem(batch, 784, 8, 10)
    with pytest.raises(ValueError, match="does not fit the fused local-SGD"):
        _engine(2, local_epochs=1, sgd_impl="kernel", local_batch_size=batch)


def test_select_frac_validation():
    with pytest.raises(ValueError, match="select_frac"):
        _engine(16, select_frac=0.25)  # below client_fraction=0.5
    with pytest.raises(ValueError, match="select_frac"):
        _engine(16, select_frac=1.5)


def test_packed_shards_mismatch_raises():
    ds = make_federated("digits", 16, scenario="iid", samples_per_client=20)
    engine = _engine(16)
    data = jax.tree.map(jnp.asarray, ds.packed_arrays(shards=4))
    with pytest.raises(ValueError, match="packed data was built"):
        engine.run(engine.init_state(), data, rounds=1)
