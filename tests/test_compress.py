"""Contract suite for the uplink compression subsystem (core/compress.py).

Pins the properties the engine integration leans on: QSGD's decode is
unbiased in expectation over keys, ``topk`` with ``k >= D`` and
``compress="none"`` are exact identities, error feedback telescopes (the
sum of decoded payloads plus the final residual equals the sum of raw
deltas to fp32 tolerance), encoding is deterministic under a fixed key,
and the all-zero / single-client edge cases behave.  Invalid-knob combos
raise actionable ``ValueError``\\ s at construction.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.common.config import FedConfig
from repro.core.compress import client_keys, make_compression

D = 96


def _fed(**kw):
    kw.setdefault("defense", "none")
    return dataclasses.replace(FedConfig(), **kw)


def _strategy(compress, dim=D, **kw):
    return make_compression(_fed(compress=compress, **kw), dim)


def _keys(seed, n):
    return client_keys(jax.random.PRNGKey(seed), jnp.arange(n, dtype=jnp.int32))


def _rows(seed, n, d=D, scale=1.0):
    return scale * jax.random.normal(jax.random.PRNGKey(seed), (n, d))


# ---------------------------------------------------------------- identities

def test_none_is_exact_identity():
    c = _strategy("none")
    assert not c.active and c.residual_dim(D) == 0
    deltas = _rows(0, 5)
    res = jnp.zeros((5, 0))
    payload, new_res = c.encode(deltas, jnp.zeros((5, D)), _keys(0, 5))
    np.testing.assert_array_equal(np.asarray(c.decode(payload, D)),
                                  np.asarray(deltas))
    assert res.shape == (5, 0)


def test_topk_k_equals_D_is_exact_identity():
    c = _strategy("topk", compress_k=D)
    deltas = _rows(1, 4)
    dec, res, _ = c.roundtrip(deltas, jnp.zeros((4, D)),
                              jnp.ones(4, bool), _keys(1, 4))
    np.testing.assert_allclose(np.asarray(dec), np.asarray(deltas), atol=0)
    np.testing.assert_allclose(np.asarray(res), 0.0, atol=0)


# ------------------------------------------------------------ qsgd unbiased

@pytest.mark.parametrize("bits", [4, 8])
def test_qsgd_decode_unbiased_over_keys(bits):
    """E_key[decode(encode(v))] == v: average the decode of ONE row over
    many independent keys; the stochastic-rounding mean error shrinks as
    1/sqrt(K) (bits=4: per-coord sd <= scale/(2*7), K=4096 -> se ~1e-3)."""
    c = _strategy("qsgd", compress_bits=bits)
    row = _rows(2, 1)
    K = 4096
    reps = jnp.broadcast_to(row, (K, D))
    keys = jax.vmap(lambda i: jax.random.fold_in(jax.random.PRNGKey(7), i))(
        jnp.arange(K)
    )
    payload, _ = c.encode(reps, jnp.zeros((K, D)), keys)
    dec = np.asarray(c.decode(payload, D))
    se = float(jnp.max(jnp.abs(row))) / (2 * (2 ** (bits - 1) - 1)) / np.sqrt(K)
    np.testing.assert_allclose(dec.mean(axis=0), np.asarray(row)[0],
                               atol=8 * se)


@pytest.mark.parametrize("bits", [4, 8])
def test_qsgd_decode_bounded_by_one_level(bits):
    """Every decoded coordinate is within one quantization level of its
    input (the deterministic guarantee underneath the unbiasedness)."""
    c = _strategy("qsgd", compress_bits=bits)
    v = _rows(3, 6)
    payload, _ = c.encode(v, jnp.zeros_like(v), _keys(3, 6))
    dec = np.asarray(c.decode(payload, D))
    scale = np.max(np.abs(np.asarray(v)), axis=-1, keepdims=True)
    level = scale / (2 ** (bits - 1) - 1)
    assert np.all(np.abs(dec - np.asarray(v)) <= level + 1e-6)


# -------------------------------------------------- error-feedback telescope

@settings(max_examples=15, deadline=None)
@given(
    mode=st.sampled_from(["qsgd4", "qsgd8", "topk"]),
    n=st.integers(1, 6),
    rounds=st.integers(1, 6),
    seed=st.integers(0, 999),
)
def test_error_feedback_telescopes(mode, n, rounds, seed):
    """sum_r decode(payload_r) + residual_final == sum_r delta_r: each
    encode consumes delta + residual and the residual carries exactly what
    the payload dropped, so compression error never accumulates."""
    c = {
        "qsgd4": lambda: _strategy("qsgd", compress_bits=4),
        "qsgd8": lambda: _strategy("qsgd", compress_bits=8),
        "topk": lambda: _strategy("topk", compress_k=7),
    }[mode]()
    res = jnp.zeros((n, D))
    total_dec = jnp.zeros((n, D))
    total_raw = jnp.zeros((n, D))
    for r in range(rounds):
        deltas = _rows(seed * 31 + r, n)
        dec, res, _ = c.roundtrip(
            deltas, res, jnp.ones(n, bool), _keys(seed + r, n)
        )
        total_dec = total_dec + dec
        total_raw = total_raw + deltas
    np.testing.assert_allclose(
        np.asarray(total_dec + res), np.asarray(total_raw),
        atol=1e-4, rtol=1e-4,
    )


@pytest.mark.parametrize(
    "kw", [dict(compress="qsgd", compress_bits=4),
           dict(compress="qsgd", compress_bits=8),
           dict(compress="topk", compress_k=7)],
)
def test_error_feedback_telescopes_deterministic(kw):
    """Fixed-seed telescoping (runs even without hypothesis installed)."""
    c = make_compression(_fed(**kw), D)
    n, rounds = 5, 6
    res = jnp.zeros((n, D))
    total_dec = jnp.zeros((n, D))
    total_raw = jnp.zeros((n, D))
    for r in range(rounds):
        deltas = _rows(100 + r, n)
        dec, res, _ = c.roundtrip(
            deltas, res, jnp.ones(n, bool), _keys(200 + r, n)
        )
        total_dec = total_dec + dec
        total_raw = total_raw + deltas
    np.testing.assert_allclose(
        np.asarray(total_dec + res), np.asarray(total_raw),
        atol=1e-4, rtol=1e-4,
    )


def test_non_transmitting_rows_keep_residual_and_send_zero():
    c = _strategy("topk", compress_k=5)
    deltas = _rows(4, 4)
    res0 = _rows(5, 4, scale=0.1)
    transmit = jnp.array([True, False, True, False])
    dec, res, _ = c.roundtrip(deltas, res0, transmit, _keys(4, 4))
    np.testing.assert_allclose(np.asarray(dec)[1], 0.0, atol=0)
    np.testing.assert_allclose(np.asarray(dec)[3], 0.0, atol=0)
    np.testing.assert_array_equal(np.asarray(res)[1], np.asarray(res0)[1])
    np.testing.assert_array_equal(np.asarray(res)[3], np.asarray(res0)[3])


# ------------------------------------------ top-k kept mask, bit for bit

_INF = np.float32(np.inf)
_NAN = np.float32(np.nan)
_NEG_NAN = np.array(0xFFC00000, np.uint32).view(np.float32)
SPECIALS = np.array([0.0, -0.0, 0.5, -0.5, 1.0, -1.0, 2.0, -2.0,
                     _INF, -_INF, _NAN, _NEG_NAN], np.float32)


def _scatter_roundtrip(k, deltas, residual, transmit):
    """The top-k roundtrip decoded from its payload: ``lax.top_k``'s
    pairs, scatter-added into zeros (``ref.topk_decode_ref``), ``v - dec``,
    then the transmit masks of ``CompressionStrategy.roundtrip``.  Also
    returns the unmasked residual ``v - dec`` that ``encode`` gives."""
    from repro.kernels.ref import topk_decode_ref

    m = transmit[:, None]
    v = (deltas + residual).astype(jnp.float32)
    _, idx = jax.lax.top_k(jnp.abs(v), k)
    payload = {"vals": jnp.take_along_axis(v, idx, axis=-1), "idx": idx}
    dec = topk_decode_ref(payload["vals"], idx, v.shape[-1])
    return ((jnp.where(m, dec, 0.0), jnp.where(m, v - dec, residual),
             payload), v - dec)


def _assert_bitwise(got, want):
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(g).view(np.int32),
                                      np.asarray(w).view(np.int32))


def _assert_kept_mask_bitwise(deltas, residual, transmit, k, impl="auto"):
    """The jitted roundtrip (as the round runs it) and encode against the
    scatter decode, each separately: the roundtrip's decoded rows and
    residuals, and its payload's shapes and dtypes; encode's payload and
    residual.  Equal as int32 views, so signed zeros and NaN bits count."""
    deltas = jnp.asarray(deltas, jnp.float32)
    residual = jnp.asarray(residual, jnp.float32)
    transmit = jnp.asarray(transmit, bool)
    c = _strategy("topk", dim=deltas.shape[-1], compress_k=k,
                  compress_impl=impl)
    want, want_res = _scatter_roundtrip(k, deltas, residual, transmit)
    shapes = []

    def run(*args):
        dec, res, payload = c.roundtrip(*args)
        shapes.append(payload)
        return dec, res

    _assert_bitwise(jax.jit(run)(deltas, residual, transmit, None),
                    want[:2])
    assert jax.tree.map(lambda a: (a.shape, a.dtype), shapes[0]) == \
        jax.tree.map(lambda a: (a.shape, a.dtype), want[2])
    payload, res = jax.jit(c.encode)(deltas, residual, None)
    _assert_bitwise(payload, want[2])
    _assert_bitwise(res, want_res)


_MZ = -0.0
KEPT_MASK_CASES = {
    # three 2s straddle k = 3: the lower-index ties are kept
    "ties_straddle_k": ([[0.5, 2.0, 1.0, 2.0, 2.0, 2.0, 0.5, 3.0]], 3),
    # magnitude ties of opposite sign at the k-th place
    "opposite_sign_ties": ([[-2.0, 2.0, -2.0, 2.0, 1.0, -1.0, 1.0, -1.0]], 3),
    # every coordinate ties; the first k are kept
    "all_equal": ([[1.5] * 8, [-1.5] * 8], 4),
    # kept and dropped zeros of both signs
    "signed_zeros": ([[_MZ, 0.0, _MZ, 1.0, _MZ, 0.0, -1.0, _MZ],
                      [_MZ] * 8], 6),
    # infinities rank above every finite value, NaN above infinity
    "inf_and_nan": ([[1.0, _INF, -2.0, -_INF, _NAN, 3.0, _NEG_NAN, 0.5],
                     [_NAN, _NEG_NAN, _NAN, 1.0, _INF, -_INF, 0.0, _MZ]], 3),
    "all_zero": ([[0.0] * 8, [_MZ] * 8], 2),
    "k_is_1": ([[0.5, -3.0, 3.0, 1.0, _MZ, -_INF, 2.0, 0.0]], 1),
    "k_is_D": ([[0.5, -3.0, 3.0, _MZ, _NAN, -_INF, 2.0, 0.0]], 8),
    # nine rows (one past a row block) of a width off the 128-lane tile,
    # quantised to five magnitudes of both signs
    "nine_rows_odd_width": ([[((r * 7 + c * 3) % 5) * 0.5 * (-1) ** c
                              for c in range(131)] for r in range(9)], 4),
    # 100 tied ones from column 1000 on, 60 kept: the last kept tie lies
    # past the first 1,024 columns
    "ties_past_a_chunk": ([[0.5] * 1000 + [1.0] * 100], 60),
}


def _kept_mask_case(case, residual, impl):
    rows, k = KEPT_MASK_CASES[case]
    deltas = np.asarray(rows, np.float32)
    n, d = deltas.shape
    if residual == "minus_zero":  # v == deltas, bit for bit
        res = np.full((n, d), _MZ, np.float32)
    else:
        rng = np.random.default_rng(sorted(KEPT_MASK_CASES).index(case))
        res = rng.choice(SPECIALS, size=(n, d))
    transmit = np.arange(n) == 0
    _assert_kept_mask_bitwise(deltas, res, transmit, k, impl)
    if n > 1:
        _assert_kept_mask_bitwise(deltas, res, np.ones(n, bool), k, impl)


@pytest.mark.parametrize("case", sorted(KEPT_MASK_CASES))
@pytest.mark.parametrize("residual", ["minus_zero", "drawn"])
def test_topk_kept_mask_matches_scatter_decode_bitwise(case, residual):
    """Fixed rows: ties at the k-th magnitude, +-0, +-inf, +-NaN, all-zero
    rows, k = 1 and k = D, nine rows of an odd width; the first row
    transmits, later ones do not.  The selection runs its ``jax.numpy``
    bisection."""
    _kept_mask_case(case, residual, "einsum")


@pytest.mark.parametrize("case", sorted(KEPT_MASK_CASES))
@pytest.mark.parametrize("residual", ["minus_zero", "drawn"])
def test_topk_kept_mask_kernel_route_matches_scatter_decode_bitwise(
        case, residual):
    """The same rows with the selection on its Pallas kernel
    (``compress_impl="kernel"``, interpreted off the chip)."""
    _kept_mask_case(case, residual, "kernel")


@settings(max_examples=25, deadline=None)
@given(
    n=st.integers(1, 5),
    d=st.integers(1, 24),
    k_frac=st.floats(0.0, 1.0),
    seed=st.integers(0, 2**31 - 1),
)
def test_topk_kept_mask_matches_scatter_decode_drawn(n, d, k_frac, seed):
    """Drawn rows of special values (heavy exact ties, +-0, +-inf, +-NaN),
    a drawn residual and a drawn transmit mask."""
    rng = np.random.default_rng(seed)
    k = 1 + int(k_frac * (d - 1))
    _assert_kept_mask_bitwise(rng.choice(SPECIALS, size=(n, d)),
                              rng.choice(SPECIALS, size=(n, d)),
                              rng.random(n) < 0.7, k)


@pytest.mark.parametrize("N,k,D", [(12, 795, 25450), (3, 1, 97), (1, 8, 8),
                                   (5, 16, 1000)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_topk_kept_mask_matches_scatter_decode_at_width(N, k, D, dtype):
    """Wide rows of normal deltas; bfloat16 deltas tie often at every
    magnitude, the k-th included."""
    key = jax.random.PRNGKey(N * 7 + k)
    deltas = jax.random.normal(key, (N, D), dtype)
    residual = jax.random.normal(jax.random.fold_in(key, 1), (N, D), dtype)
    _assert_kept_mask_bitwise(deltas, residual, np.arange(N) % 3 != 1, k)


def test_topk_decode_received_payload_accumulates_duplicates():
    """``decode`` of a received payload scatter-ADDs: a repeated index
    sums its values, and zero-valued pairs decode to exact zeros."""
    c = _strategy("topk", dim=8, compress_k=3)
    got = c.decode({"vals": jnp.array([[1.0, 2.0, 3.0], [0.0, 0.0, 0.0]]),
                    "idx": jnp.array([[5, 5, 2], [1, 4, 7]], jnp.int32)}, 8)
    want = np.zeros((2, 8), np.float32)
    want[0, 5], want[0, 2] = 3.0, 3.0
    np.testing.assert_array_equal(np.asarray(got), want)


def test_topk_decode_received_payload_inverts_encode():
    """A received payload decodes to the rows the round kept."""
    c = _strategy("topk", compress_k=7)
    deltas, residual = _rows(3, 4), _rows(4, 4, scale=0.1)
    payload, _ = c.encode(deltas, residual, None)
    dec, _, _ = c.roundtrip(deltas, residual, jnp.ones(4, bool), None)
    np.testing.assert_array_equal(np.asarray(c.decode(payload, D)),
                                  np.asarray(dec))


# ------------------------------------------------------------- determinism

@pytest.mark.parametrize(
    "kw", [dict(compress="qsgd", compress_bits=4),
           dict(compress="qsgd", compress_bits=8),
           dict(compress="topk", compress_k=9)],
)
def test_fixed_key_is_deterministic(kw):
    c = make_compression(_fed(**kw), D)
    deltas, res = _rows(6, 3), _rows(7, 3, scale=0.01)
    out1 = c.roundtrip(deltas, res, jnp.ones(3, bool), _keys(11, 3))
    out2 = c.roundtrip(deltas, res, jnp.ones(3, bool), _keys(11, 3))
    for a, b in zip(jax.tree.leaves(out1), jax.tree.leaves(out2)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# --------------------------------------------------------------- edge cases

@pytest.mark.parametrize(
    "kw", [dict(compress="qsgd", compress_bits=4),
           dict(compress="qsgd", compress_bits=8),
           dict(compress="topk", compress_k=3)],
)
def test_all_zero_rows_stay_exactly_zero(kw):
    c = make_compression(_fed(**kw), D)
    z = jnp.zeros((2, D))
    dec, res, _ = c.roundtrip(z, z, jnp.ones(2, bool), _keys(0, 2))
    np.testing.assert_array_equal(np.asarray(dec), 0.0)
    np.testing.assert_array_equal(np.asarray(res), 0.0)


def test_single_client_roundtrip():
    c = _strategy("qsgd", compress_bits=8)
    deltas = _rows(8, 1)
    dec, res, payload = c.roundtrip(
        deltas, jnp.zeros((1, D)), jnp.ones(1, bool), _keys(9, 1)
    )
    np.testing.assert_allclose(np.asarray(dec + res), np.asarray(deltas),
                               atol=1e-6, rtol=1e-6)
    assert payload["codes"].shape[0] == 1


# -------------------------------------------------------- payload accounting

def test_payload_nbytes_hits_nominal_ratios():
    dense = _strategy("none").payload_nbytes(25450)
    q8 = _strategy("qsgd", compress_bits=8).payload_nbytes(25450)
    q4 = _strategy("qsgd", compress_bits=4).payload_nbytes(25450)
    tk = _strategy("topk", compress_k=795, dim=25450).payload_nbytes(25450)
    assert dense == 4 * 25450
    assert q8 <= dense / 2  # acceptance: >= 2x reduction at 8 bits
    assert q4 <= dense / 4  # >= 4x at 4 bits
    assert tk == 8 * 795


# ------------------------------------------------------- validation errors

def test_unknown_compress_name_raises():
    with pytest.raises(ValueError, match="unknown FedConfig.compress"):
        make_compression(_fed(compress="gzip"), D)


def test_bad_bits_raises():
    with pytest.raises(ValueError, match="compress_bits"):
        make_compression(_fed(compress="qsgd", compress_bits=3), D)


@pytest.mark.parametrize("k", [0, -1, D + 1])
def test_bad_k_raises(k):
    with pytest.raises(ValueError, match="compress_k"):
        make_compression(_fed(compress="topk", compress_k=k), D)


@pytest.mark.parametrize("compress", ["qsgd", "topk"])
def test_async_seq_combo_raises(compress):
    with pytest.raises(ValueError, match="does not compose"):
        make_compression(_fed(compress=compress, aggregation="async_seq"), D)


def test_engine_runs_buffered_async_with_compression():
    """aggregation='async' + qsgd composes: clients transmit on the
    client-side-knowable window (lag-0 or free slot, a superset of admit)
    and the error-feedback residual stays finite across the buffer."""
    from repro.configs.fedar_mnist import fleet_fed, small_model
    from repro.core.engine import FedAREngine
    from repro.core.resources import TaskRequirement
    from repro.data.federated import scaled_fleet

    n = 12
    fed = fleet_fed(n, local_epochs=1, aggregation="async", compress="qsgd",
                    compress_bits=8, defense="none")
    eng = FedAREngine(small_model(16), fed, TaskRequirement())
    data = {k: jnp.asarray(v)
            for k, v in scaled_fleet(n, samples_per_client=40).items()}
    state, outs = eng.run(eng.init_state(), data, rounds=3)
    assert np.isfinite(np.asarray(state.params)).all()
    assert np.isfinite(np.asarray(state.compress_residual)).all()
    # the model actually moved — compression didn't zero the uplink
    assert float(jnp.abs(state.params - eng.init_state().params).sum()) > 0


@pytest.mark.parametrize("compress,impl,route", [
    ("none", "kernel", "none"),
    ("topk", "auto", "einsum"),
    ("topk", "kernel", "kernel"),
    ("qsgd", "kernel", "kernel"),
    ("qsgd", "einsum", "einsum"),
])
def test_kernel_routes_name_the_codec_path(compress, impl, route):
    """``kernel_routes`` reports what the round runs: top-k's selection
    and qsgd's pack take the knob (``auto`` is XLA off the chip)."""
    from repro.configs.fedar_mnist import fleet_fed, small_model
    from repro.core.engine import FedAREngine
    from repro.core.resources import TaskRequirement

    fed = fleet_fed(6, compress=compress, compress_impl=impl, defense="none")
    eng = FedAREngine(small_model(8), fed, TaskRequirement())
    assert eng.kernel_routes()["compress"] == route
