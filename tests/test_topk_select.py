"""The top-k selection (``kernels/topk_select.py``): each row's k-th key
and last kept tie, by the Pallas kernel (interpreted off the chip) and by
its ``jax.numpy`` oracle, equal to what ``lax.top_k`` implies.

``lax.top_k(|v|, k)`` keeps the k largest magnitudes, ties toward the
lower column; its last index is the last kept tie and the key there is
the k-th key.  The rows are chosen to make that hard: ties everywhere,
+-0, +-inf and NaN of both signs, quantised values, widths off the
128-lane tile, row counts off the 8-row block, and k at 1, D // 32 and D.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.kernels import ops
from repro.kernels.ref import topk_select_ref
from repro.kernels.topk_select import (
    ROWS,
    col_bits,
    topk_select,
    topk_select_fits_vmem,
)

_NEG_NAN = np.array(0xFFC00000, np.uint32).view(np.float32)
SPECIALS = np.array([0.0, -0.0, 0.5, -0.5, 1.0, -1.0, 2.0, -2.0, np.inf,
                     -np.inf, np.nan, _NEG_NAN], np.float32)


def implied(v, k):
    """``(thr, last)`` as ``lax.top_k`` gives them."""
    a = jnp.abs(jnp.asarray(v, jnp.float32))
    _, idx = jax.lax.top_k(a, k)
    last = idx[:, -1:].astype(jnp.int32)
    key = jax.lax.bitcast_convert_type(a, jnp.int32)
    return jnp.take_along_axis(key, last, axis=-1), last


def _draw(kind, rng, n, d):
    if kind == "specials":
        return rng.choice(SPECIALS, size=(n, d))
    if kind == "quantised":  # a handful of magnitudes: ties at every k
        return (np.round(rng.standard_normal((n, d)) * 2) / 2).astype(
            np.float32)
    if kind == "tied":  # one magnitude, both signs and both zeros
        return rng.choice(np.float32([1.5, -1.5, 0.0, -0.0]), size=(n, d))
    return rng.standard_normal((n, d)).astype(np.float32)


def _assert_selects(v, k):
    want = implied(v, k)
    v = jnp.asarray(v, jnp.float32)
    for got in (topk_select(v, k=k, interpret=True), topk_select_ref(v, k)):
        for g, w in zip(got, want):
            assert g.shape == w.shape and g.dtype == jnp.int32
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@pytest.mark.parametrize("kind", ["gauss", "specials", "quantised", "tied"])
@pytest.mark.parametrize("n,d", [(1, 1), (5, 7), (8, 128), (9, 131),
                                 (3, 1100)])
def test_selection_matches_top_k(kind, n, d):
    rng = np.random.default_rng([n, d, len(kind)])
    v = _draw(kind, rng, n, d)
    for k in sorted({1, max(1, d // 32), d}):
        _assert_selects(v, k)


@settings(max_examples=20, deadline=None)
@given(
    kind=st.sampled_from(["gauss", "specials", "quantised", "tied"]),
    n=st.integers(1, 17),
    d=st.sampled_from([1, 2, 3, 31, 129, 300]),
    which_k=st.sampled_from(["one", "d32", "d"]),
    seed=st.integers(0, 2**31 - 1),
)
def test_selection_matches_top_k_drawn(kind, n, d, which_k, seed):
    """Drawn rows: every kind of tie and special value, row counts off the
    8-row block, widths off the 128-lane tile, k in {1, D // 32, D}."""
    k = {"one": 1, "d32": max(1, d // 32), "d": d}[which_k]
    _assert_selects(_draw(kind, np.random.default_rng(seed), n, d), k)


def test_all_zero_rows_keep_their_first_k_columns():
    """Every key of a zero row is 0 and ties: the first k columns are
    kept, whatever the zeros' signs."""
    v = jnp.asarray([[0.0, -0.0] * 10] * 3, jnp.float32)
    thr, last = topk_select(v, k=6, interpret=True)
    np.testing.assert_array_equal(np.asarray(thr), 0)
    np.testing.assert_array_equal(np.asarray(last), 5)


def test_column_bisection_spans_every_column():
    """``col_bits`` steps reach column D - 1 and no further."""
    for d in (1, 2, 3, 4, 5, 127, 128, 129, 101770):
        b = col_bits(d)
        assert (1 << b) >= d and (b == 0 or (1 << (b - 1)) < d)


def test_vmem_budget_admits_the_paper_mlp_and_refuses_lm_widths():
    """An 8-row block of the paper's MLP (D = 101,770) fits the kernel's
    VMEM; a row of half a million parameters does not."""
    assert ROWS == 8
    assert topk_select_fits_vmem(101770)
    assert not topk_select_fits_vmem(500_000)


def test_ops_routes_between_kernel_and_oracle():
    v = jnp.asarray(_draw("quantised", np.random.default_rng(3), 4, 40))
    want = implied(v, 5)
    for use_pallas in (False, True):
        got = ops.topk_select(v, k=5, use_pallas=use_pallas)
        for g, w in zip(got, want):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
