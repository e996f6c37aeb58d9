"""Scan-engine validation: scan vs python-loop numerics, buffered async,
fleet-size parameterization."""
import jax.numpy as jnp
import numpy as np

from repro.common.config import FedConfig
from repro.configs.fedar_mnist import MnistConfig, fleet_fed, small_model
from repro.core.engine import FedAREngine
from repro.core.fedar import FedARServer
from repro.core.resources import TaskRequirement, check_resource, make_fleet
from repro.data.federated import scaled_fleet, table2_fleet
from repro.data.synthetic import make_digits

ROUNDS = 5


def _data(samples=200, seed=0):
    data = table2_fleet(samples_per_client=samples, seed=seed)
    return {k: jnp.asarray(v) for k, v in data.items()}


def _servers(aggregation="fedar"):
    fed = FedConfig(num_clients=12, local_epochs=2, timeout=8.0,
                    aggregation=aggregation)
    return (FedARServer(MnistConfig(), fed, TaskRequirement()),
            FedARServer(MnistConfig(), fed, TaskRequirement()))


def test_client_mesh_raises_on_too_few_devices():
    """A mesh wider than the host's devices is an error, never a narrower
    or dropped mesh."""
    import jax
    import pytest

    from repro.core.distributed import client_mesh

    want = len(jax.devices()) + 1
    with pytest.raises(ValueError, match=f"mesh_shape={want} requested"):
        client_mesh(fleet_fed(12 * want, mesh_shape=want))
    assert client_mesh(fleet_fed(12, mesh_shape=1)) is None


def test_scan_matches_python_driver_trust_and_loss():
    """Acceptance bar: the scan engine reproduces the per-round driver's
    trust/accuracy histories within 1e-4 on the 12-robot MNIST config."""
    srv_scan, srv_py = _servers()
    data = _data()
    ex, ey = make_digits(400, seed=99)
    force = np.zeros(12, bool)
    force[0] = True
    h_scan = srv_scan.run(data, rounds=ROUNDS, eval_set=(ex, ey),
                          force_straggler=force, driver="scan")
    h_py = srv_py.run(data, rounds=ROUNDS, eval_set=(ex, ey),
                      force_straggler=force, driver="python")
    np.testing.assert_allclose(np.stack(h_scan["trust"]),
                               np.stack(h_py["trust"]), atol=1e-4)
    np.testing.assert_allclose(h_scan["loss"], h_py["loss"], atol=1e-4)
    np.testing.assert_allclose(h_scan["acc"], h_py["acc"], atol=1e-4)
    np.testing.assert_array_equal(np.stack(h_scan["selected"]),
                                  np.stack(h_py["selected"]))
    np.testing.assert_array_equal(np.stack(h_scan["on_time"]),
                                  np.stack(h_py["on_time"]))


def test_scan_matches_python_driver_buffered_async():
    srv_scan, srv_py = _servers(aggregation="async")
    data = _data()
    ex, ey = make_digits(400, seed=99)
    h_scan = srv_scan.run(data, rounds=ROUNDS, eval_set=(ex, ey))
    h_py = srv_py.run(data, rounds=ROUNDS, eval_set=(ex, ey),
                      driver="python")
    np.testing.assert_allclose(np.stack(h_scan["trust"]),
                               np.stack(h_py["trust"]), atol=1e-4)
    np.testing.assert_allclose(h_scan["loss"], h_py["loss"], atol=1e-4)


def test_buffered_async_merges_straggler_updates_late():
    """No-wait semantics: a permanent straggler's update is NOT discarded —
    it sits in the buffer and merges (staleness-discounted) rounds later."""
    fed = FedConfig(num_clients=12, local_epochs=2, timeout=8.0,
                    aggregation="async", selection="random")
    engine = FedAREngine(MnistConfig(), fed, TaskRequirement())
    data = _data()
    force = np.zeros(12, bool)
    force[:6] = True  # lat = 3 * timeout -> arrival 3 rounds later
    state = engine.init_state()
    deliveries = 0
    for _ in range(6):
        pending_before = np.asarray(state.pending_valid)
        state, out = engine.step(state, data,
                                 force_straggler=jnp.asarray(force))
        pending_after = np.asarray(state.pending_valid)
        # a slot clearing without being re-admitted == a late delivery
        deliveries += int((pending_before & ~pending_after).sum())
    assert np.asarray(state.pending_valid).sum() + deliveries > 0
    assert deliveries > 0  # at least one straggler update landed late


def test_buffered_async_converges():
    srv, _ = _servers(aggregation="async")
    data = _data()
    ex, ey = make_digits(400, seed=99)
    h = srv.run(data, rounds=8, eval_set=(ex, ey))
    assert h["acc"][-1] > h["acc"][0]


def test_engine_runs_at_large_fleet_sizes():
    """Fleet size is a parameter, not a constant: N=64 end-to-end."""
    n = 64
    fed = fleet_fed(n, local_epochs=1, foolsgold=False, aggregation="async")
    engine = FedAREngine(small_model(32), fed, TaskRequirement())
    data = {k: jnp.asarray(v)
            for k, v in scaled_fleet(n, samples_per_client=40).items()}
    state, outs = engine.run(engine.init_state(), data, rounds=3)
    assert outs.trust.shape == (3, n)
    assert int(outs.selected[0].sum()) == max(1, int(n * fed.client_fraction))


def test_make_fleet_scales_heterogeneity_mix():
    res, poison = make_fleet(48, seed=0)
    # paper fractions: 1/6 starved, 1/6 poisoners at any N
    assert poison.sum() == 8
    ra = np.asarray(check_resource(res, TaskRequirement()))
    assert (~ra[32:40]).all()  # the 8 starved robots fail CheckResource
    res12, poison12 = make_fleet(12, seed=0)
    assert poison12.sum() == 2  # the paper's exact 12-robot mix is unchanged


def test_scaled_fleet_matches_make_fleet_poisoners():
    n = 36
    data = scaled_fleet(n, samples_per_client=50, seed=0)
    _, poison = make_fleet(n, seed=0)
    assert data["x"].shape[0] == n
    assert poison[-6:].all() and not poison[:-6].any()


def test_run_round_then_run_continues_rounds():
    """Mixing the per-round and scan drivers keeps one consistent history."""
    srv, ref = _servers()
    data = _data()
    srv.run_round(data)
    srv.run(data, rounds=2)
    ref.run(data, rounds=3)
    assert srv.round_idx == ref.round_idx == 3
    np.testing.assert_allclose(np.stack(srv.history["trust"]),
                               np.stack(ref.history["trust"]), atol=1e-4)
