"""ClientModel protocol + public API surface tests.

Covers the aggregation-boundary adapter (``flatten``/``unflatten``
round-trip over arbitrary nested pytrees — hypothesis when installed, a
seeded random-tree sweep otherwise), engine parity between the seed
``MnistConfig`` surface and the explicit ``MnistClientModel``, the shared
``resolve_impl`` helper, the legacy-bool deprecation path, the kernel
fallback warning, and the ``repro`` facade exports."""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import (
    ClientModel,
    FedAREngine,
    FedARServer,
    LMClientModel,
    MnistClientModel,
    TaskRequirement,
    make_federated,
)
from repro.configs import get_config
from repro.configs.fedar_mnist import MnistConfig, fleet_fed, small_model
from repro.core.engine import flatten, unflatten
from repro.kernels.ops import resolve_impl

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

def random_tree(rng, depth=2):
    """A random nested pytree of float arrays: dict/list/tuple containers,
    mixed shapes and dtypes (f32/bf16/f16 — everything that round-trips
    exactly through the f32 flat view)."""
    dtypes = (jnp.float32, jnp.bfloat16, jnp.float16)

    def leaf():
        shape = tuple(int(rng.integers(1, 5))
                      for _ in range(int(rng.integers(0, 4))))
        dt = dtypes[int(rng.integers(len(dtypes)))]
        return jnp.asarray(
            rng.standard_normal(shape).astype(np.float32)
        ).astype(dt)

    def node(d):
        if d == 0 or rng.random() < 0.3:
            return leaf()
        kind = int(rng.integers(3))
        n = int(rng.integers(1, 4))
        children = [node(d - 1) for _ in range(n)]
        if kind == 0:
            return {f"k{i}": c for i, c in enumerate(children)}
        return tuple(children) if kind == 1 else list(children)

    # guarantee at least one leaf
    t = node(depth)
    return t if jax.tree.leaves(t) else leaf()


def assert_roundtrip(tree):
    flat = flatten(tree)
    assert flat.ndim == 1
    back = unflatten(flat, tree)
    la, lb = jax.tree.leaves(tree), jax.tree.leaves(back)
    assert jax.tree.structure(tree) == jax.tree.structure(back)
    for a, b in zip(la, lb):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


if HAVE_HYPOTHESIS:

    @given(st.integers(0, 2**32 - 1))
    @settings(max_examples=50, deadline=None)
    def test_flatten_unflatten_roundtrip(seed):
        assert_roundtrip(random_tree(np.random.default_rng(seed)))

else:

    @pytest.mark.parametrize("seed", range(25))
    def test_flatten_unflatten_roundtrip(seed):
        assert_roundtrip(random_tree(np.random.default_rng(seed)))


def test_flatten_unflatten_lm_params():
    """The real transformer pytree survives the aggregation boundary."""
    cfg = get_config("tinyllama-1.1b").reduced(
        num_layers=1, d_model=64, d_ff=128, vocab_size=128,
        num_heads=2, num_kv_heads=1,
    )
    model = LMClientModel(cfg)
    params = model.init(jax.random.PRNGKey(0))
    assert_roundtrip(params)


def test_engine_parity_mnist_config_vs_client_model():
    """FedAREngine(MnistConfig) and FedAREngine(MnistClientModel(cfg)) are
    the same engine: identical params / trust / history bit for bit — the
    seed API is a pure coercion, so the paper-exact N=12 goldens pin BOTH
    construction paths."""
    fed = fleet_fed(12, defense="foolsgold_sketch")
    ds = make_federated("table2", 12, samples_per_client=60)
    data = {k: jnp.asarray(v) for k, v in ds.arrays().items()}
    cfg = small_model(32)

    finals = []
    for model in (cfg, MnistClientModel(cfg)):
        engine = FedAREngine(model, fed, TaskRequirement())
        state = engine.init_state()
        state, outs = engine.run(state, data, rounds=3)
        finals.append(state)
    a, b = finals
    np.testing.assert_array_equal(np.asarray(a.params), np.asarray(b.params))
    np.testing.assert_array_equal(np.asarray(a.trust), np.asarray(b.trust))
    np.testing.assert_array_equal(np.asarray(a.fg_history),
                                  np.asarray(b.fg_history))


def test_resolve_impl():
    assert resolve_impl("kernel", "sgd") == "kernel"
    assert resolve_impl("einsum", "agg") == "einsum"
    auto = resolve_impl("auto", "defense")
    assert auto == ("kernel" if jax.default_backend() == "tpu" else "einsum")
    with pytest.raises(ValueError, match="unknown sgd_impl 'pallas'"):
        resolve_impl("pallas", "sgd")
    with pytest.raises(ValueError, match="unknown impl kind"):
        resolve_impl("auto", "matmul")


def test_legacy_foolsgold_bool_deprecated():
    from repro.core.defense import make_defense

    fed = fleet_fed(8)  # defense=None: legacy bool resolution path
    assert fed.defense is None
    with pytest.warns(DeprecationWarning, match="legacy FedConfig.foolsgold"):
        make_defense(fed, 16)
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        make_defense(fleet_fed(8, defense="none"), 16)


def test_kernel_request_falls_back_without_fused_model():
    """sgd_impl="kernel" on a family with no fused Pallas local-SGD kernel
    warns and runs the vmapped XLA path instead of crashing."""
    cfg = get_config("tinyllama-1.1b").reduced(
        num_layers=1, d_model=32, d_ff=64, vocab_size=64,
        num_heads=2, num_kv_heads=1,
    )
    fed = fleet_fed(4, sgd_impl="kernel", defense="none",
                    local_epochs=1, local_batch_size=4)
    with pytest.warns(UserWarning, match="falling back to the vmapped"):
        engine = FedAREngine(LMClientModel(cfg), fed, TaskRequirement())
    assert engine.sgd_route == "xla"


def test_lm_model_rejects_packed_layout():
    cfg = get_config("tinyllama-1.1b").reduced(
        num_layers=1, d_model=32, d_ff=64, vocab_size=64,
        num_heads=2, num_kv_heads=1,
    )
    fed = fleet_fed(4, defense="none")
    engine = FedAREngine(LMClientModel(cfg), fed, TaskRequirement())
    state = engine.init_state()
    with pytest.raises(ValueError, match="does not support the bucketed"):
        engine.step(state, {"packed": {"shards": 1}})


def test_facade_exports():
    import repro

    expected = {"ClientModel", "FedAREngine", "FedARServer", "FedConfig",
                "LMClientModel", "MnistClientModel", "TaskRequirement",
                "make_federated"}
    assert expected == set(repro.__all__)
    for name in repro.__all__:
        assert getattr(repro, name) is not None
    # deep imports keep working alongside the facade
    from repro.core.engine import FedAREngine as deep

    assert deep is FedAREngine
    assert isinstance(MnistClientModel(MnistConfig()), ClientModel)
    assert FedARServer is not None
