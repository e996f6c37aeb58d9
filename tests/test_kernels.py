"""Pallas kernel validation: shape/dtype sweeps vs pure-jnp oracles
(interpret=True executes the kernel bodies on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.kernels import ref
from repro.kernels.compress import pack_codes, unpack_codes
from repro.kernels.defense_sim import sketch_similarity
from repro.kernels.fedavg_agg import fedavg_agg
from repro.kernels.flash_attention import flash_attention
from repro.kernels.local_sgd import fused_fits_vmem, local_sgd_fused
from repro.kernels.ssm_scan import ssm_scan


# ---------------------------------------------------------------------------
# fedavg_agg
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("N,D", [(12, 1000), (64, 8192), (3, 97), (1, 2048), (256, 4096)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fedavg_agg_sweep(N, D, dtype):
    k = jax.random.PRNGKey(N * 7 + D)
    deltas = jax.random.normal(k, (N, D), dtype)
    w = jax.random.uniform(jax.random.fold_in(k, 1), (N,))
    got = fedavg_agg(deltas, w, interpret=True)
    want = ref.fedavg_agg_ref(deltas, w)
    tol = 1e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol)


@settings(max_examples=10, deadline=None)
@given(n=st.integers(1, 40), d=st.integers(1, 500), seed=st.integers(0, 99))
def test_fedavg_agg_property(n, d, seed):
    k = jax.random.PRNGKey(seed)
    deltas = jax.random.normal(k, (n, d))
    w = jax.random.uniform(jax.random.fold_in(k, 1), (n,))
    got = fedavg_agg(deltas, w, interpret=True, block_d=256)
    np.testing.assert_allclose(got, ref.fedavg_agg_ref(deltas, w),
                               rtol=1e-4, atol=1e-4)


def test_fedavg_agg_zero_weights():
    deltas = jnp.ones((4, 100))
    got = fedavg_agg(deltas, jnp.zeros(4), interpret=True)
    assert np.allclose(got, 0.0)


def test_fedavg_agg_padded_tail():
    """D not a multiple of block_d: the zero-padded tail must not leak."""
    N, D, block = 7, 1000, 256  # 1000 = 3*256 + 232
    k = jax.random.PRNGKey(0)
    deltas = jax.random.normal(k, (N, D))
    w = jax.random.uniform(jax.random.fold_in(k, 1), (N,))
    got = fedavg_agg(deltas, w, interpret=True, block_d=block)
    assert got.shape == (D,)
    np.testing.assert_allclose(got, ref.fedavg_agg_ref(deltas, w),
                               rtol=1e-5, atol=1e-5)


def test_fedavg_agg_single_client():
    """N=1 degenerates to a scaled copy of the one delta row."""
    k = jax.random.PRNGKey(2)
    deltas = jax.random.normal(k, (1, 300))
    got = fedavg_agg(deltas, jnp.array([2.5]), interpret=True, block_d=128)
    np.testing.assert_allclose(got, 2.5 * deltas[0], rtol=1e-5, atol=1e-5)


def test_fedavg_agg_bf16_vs_fp32_oracle():
    """bf16 deltas accumulate in fp32 inside the kernel."""
    k = jax.random.PRNGKey(3)
    deltas32 = jax.random.normal(k, (24, 900))
    w = jax.random.uniform(jax.random.fold_in(k, 1), (24,))
    got = fedavg_agg(deltas32.astype(jnp.bfloat16), w, interpret=True,
                     block_d=256)
    assert got.dtype == jnp.float32
    want = ref.fedavg_agg_ref(deltas32.astype(jnp.bfloat16), w)
    np.testing.assert_allclose(got, want, rtol=3e-2, atol=3e-2)


def test_fedavg_agg_large_fleet_shrinks_block():
    """At N=4096 the tile must narrow to keep the VMEM slab bounded, and the
    result must still match the oracle."""
    from repro.kernels.fedavg_agg import VMEM_BUDGET_BYTES, _fit_block

    assert _fit_block(4096, 2048) * 4096 * 4 <= VMEM_BUDGET_BYTES
    assert _fit_block(12, 2048) == 2048  # small fleets keep the wide tile
    k = jax.random.PRNGKey(7)
    deltas = jax.random.normal(k, (4096, 300))
    w = jax.random.uniform(jax.random.fold_in(k, 1), (4096,))
    got = fedavg_agg(deltas, w, interpret=True)
    np.testing.assert_allclose(got, ref.fedavg_agg_ref(deltas, w),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("N,D,block", [(5, 97, 64), (16, 2048, 2048)])
def test_fedavg_agg_staleness_decay(N, D, block):
    """The fused (1 + tau)^-0.5 staleness discount matches the oracle."""
    k = jax.random.PRNGKey(N + D)
    deltas = jax.random.normal(k, (N, D))
    w = jax.random.uniform(jax.random.fold_in(k, 1), (N,))
    tau = jax.random.randint(jax.random.fold_in(k, 2), (N,), 0, 5)
    tau = tau.astype(jnp.float32)
    got = fedavg_agg(deltas, w, staleness=tau, interpret=True, block_d=block)
    want = ref.fedavg_agg_ref(deltas, w, staleness=tau)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    # tau=0 must equal the undecayed path
    got0 = fedavg_agg(deltas, w, staleness=jnp.zeros(N), interpret=True,
                      block_d=block)
    np.testing.assert_allclose(got0, ref.fedavg_agg_ref(deltas, w),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# fused local SGD
# ---------------------------------------------------------------------------

def _mlp(key, inp=16, hid=8, classes=10):
    k1, k2 = jax.random.split(key)
    return (jax.random.normal(k1, (inp, hid)) * 0.3, jnp.zeros((hid,)),
            jax.random.normal(k2, (hid, classes)) * 0.3, jnp.zeros((classes,)))


@pytest.mark.parametrize("act", [0, 1])
@pytest.mark.parametrize("n,bs,epochs", [(40, 20, 2), (37, 10, 3), (8, 20, 1)])
def test_local_sgd_fused_matches_oracle_and_model(act, n, bs, epochs):
    """The hand-written fused backward pass == jax.grad (the ref oracle AND
    models.mnist.local_sgd), per Table II activation, ragged tails incl."""
    from repro.models.mnist import local_sgd as model_sgd

    w1, b1, w2, b2 = _mlp(jax.random.PRNGKey(act * 7 + n))
    k = jax.random.PRNGKey(n + bs)
    R = 3
    x = jax.random.normal(jax.random.fold_in(k, 0), (R, n, 16))
    y = jax.random.randint(jax.random.fold_in(k, 1), (R, n), 0, 10)
    acts = jnp.full((R,), act, jnp.int32)
    # ragged: full, partial, and tiny shards
    n_u = jnp.array([n, max(1, n // 2), 1])[:R]
    mask = jnp.arange(n)[None, :] < n_u[:, None]
    got = local_sgd_fused(w1, b1, w2, b2, x, y, acts, mask, lr=0.1,
                          batch_size=bs, epochs=epochs, interpret=True)
    for i in range(R):
        want = ref.local_sgd_ref(w1, b1, w2, b2, x[i], y[i], acts[i],
                                 mask[i], lr=0.1, batch_size=bs,
                                 epochs=epochs)
        model = model_sgd(
            {"w1": w1, "b1": b1, "w2": w2, "b2": b2}, x[i], y[i], lr=0.1,
            batch_size=bs, epochs=epochs, activation=acts[i],
            sample_mask=mask[i],
        )
        for kk in ("w1", "b1", "w2", "b2"):
            np.testing.assert_allclose(got[kk][i], want[kk], rtol=1e-5,
                                       atol=1e-5)
            np.testing.assert_allclose(got[kk][i], model[kk], rtol=1e-5,
                                       atol=1e-5)


def test_local_sgd_fused_all_masked_is_noop():
    """A fully-masked client (dummy mesh-fill row / empty shard) must come
    back with the global params untouched — its delta is exactly zero."""
    w1, b1, w2, b2 = _mlp(jax.random.PRNGKey(3))
    k = jax.random.PRNGKey(9)
    x = jax.random.normal(k, (1, 24, 16))
    y = jnp.zeros((1, 24), jnp.int32)
    got = local_sgd_fused(w1, b1, w2, b2, x, y, jnp.zeros((1,), jnp.int32),
                          jnp.zeros((1, 24), bool), lr=0.1, batch_size=20,
                          epochs=2, interpret=True)
    np.testing.assert_array_equal(got["w1"][0], w1)
    np.testing.assert_array_equal(got["b1"][0], b1)
    np.testing.assert_array_equal(got["w2"][0], w2)
    np.testing.assert_array_equal(got["b2"][0], b2)


def test_local_sgd_fused_dense_equals_unmasked_model_path():
    """With an all-True mask and batch-aligned n, the kernel matches the
    dense (maskless) model path — the masked renormalization degenerates to
    the plain batch mean."""
    from repro.models.mnist import local_sgd as model_sgd

    w1, b1, w2, b2 = _mlp(jax.random.PRNGKey(5))
    k = jax.random.PRNGKey(6)
    x = jax.random.normal(k, (2, 40, 16))
    y = jax.random.randint(jax.random.fold_in(k, 1), (2, 40), 0, 10)
    acts = jnp.array([0, 1], jnp.int32)
    got = local_sgd_fused(w1, b1, w2, b2, x, y, acts,
                          jnp.ones((2, 40), bool), lr=0.05, batch_size=20,
                          epochs=2, interpret=True)
    for i in range(2):
        dense = model_sgd(
            {"w1": w1, "b1": b1, "w2": w2, "b2": b2}, x[i], y[i], lr=0.05,
            batch_size=20, epochs=2, activation=acts[i],
        )
        for kk in ("w1", "b1", "w2", "b2"):
            np.testing.assert_allclose(got[kk][i], dense[kk], rtol=1e-5,
                                       atol=1e-5)


def test_fused_fits_vmem_bounds():
    """The VMEM estimate admits the paper's model at bucket widths and
    rejects slabs that cannot fit."""
    assert fused_fits_vmem(512, 784, 128, 10)
    assert not fused_fits_vmem(65536, 784, 128, 10)


def _ragged_inputs(buckets, bs):
    """Tile mixed-width buckets of (x, y, mask, act) into the flat
    batch-tile buffer + per-row (nb, off) geometry the ragged kernel takes
    (mirrors models.mnist.fused_ragged_update)."""
    xts, yts, mts, acts, nbs = [], [], [], [], []
    for x, y, m, a in buckets:
        rows, w = x.shape[0], x.shape[1]
        nb = w // bs
        xts.append(x.reshape(rows * nb, bs, -1))
        yts.append(y.reshape(rows * nb, bs))
        mts.append(m.astype(jnp.float32).reshape(rows * nb, bs))
        acts.append(a)
        nbs.append(np.full(rows, nb, np.int32))
    nb_arr = np.concatenate(nbs)
    off = np.concatenate([[0], np.cumsum(nb_arr)[:-1]]).astype(np.int32)
    return (jnp.concatenate(xts), jnp.concatenate(yts),
            jnp.concatenate(mts), jnp.concatenate(acts),
            jnp.asarray(nb_arr), jnp.asarray(off))


def test_local_sgd_fused_ragged_matches_per_bucket():
    """ONE ragged-grid launch over mixed-width buckets is bit-equal to the
    per-bucket ``local_sgd_fused`` dispatch loop it replaces — including a
    fully-masked dummy row (mesh fill) and buckets whose batch count sits
    below ``nb_max`` (the grid's tail steps must be true no-ops)."""
    from repro.kernels.local_sgd import local_sgd_fused_ragged

    w1, b1, w2, b2 = _mlp(jax.random.PRNGKey(11))
    bs = 4
    k = jax.random.PRNGKey(12)
    buckets = []
    for bi, (rows, width) in enumerate([(2, 8), (3, 16), (2, 4)]):
        kk = jax.random.fold_in(k, bi)
        x = jax.random.normal(jax.random.fold_in(kk, 0), (rows, width, 16))
        y = jax.random.randint(jax.random.fold_in(kk, 1), (rows, width),
                               0, 10)
        m = jax.random.bernoulli(jax.random.fold_in(kk, 2), 0.8,
                                 (rows, width))
        a = jax.random.randint(jax.random.fold_in(kk, 3), (rows,), 0, 2)
        buckets.append([x, y, m, a])
    buckets[0][2] = buckets[0][2].at[1].set(False)  # dummy: all-masked row
    buckets[2][2] = buckets[2][2].at[0].set(False)  # all-masked whole batch
    xt, yt, mt, act, nb_arr, off = _ragged_inputs(buckets, bs)
    got = local_sgd_fused_ragged(
        w1, b1, w2, b2, xt, yt, mt, act, nb_arr, off,
        lr=0.1, epochs=2, nb_max=int(np.asarray(nb_arr).max()),
        interpret=True,
    )
    r0 = 0
    for x, y, m, a in buckets:
        want = local_sgd_fused(w1, b1, w2, b2, x, y, a, m, lr=0.1,
                               batch_size=bs, epochs=2, interpret=True)
        for kk_ in ("w1", "b1", "w2", "b2"):
            np.testing.assert_array_equal(
                np.asarray(got[kk_][r0:r0 + x.shape[0]]),
                np.asarray(want[kk_]),
            )
        r0 += x.shape[0]
    # the dummy rows specifically came back as the untouched globals
    np.testing.assert_array_equal(np.asarray(got["w1"][1]), np.asarray(w1))


# ---------------------------------------------------------------------------
# defense similarity block product
# ---------------------------------------------------------------------------

@pytest.mark.parametrize(
    "M,Nf,K",
    [(16, 128, 256), (8, 64, 256), (128, 128, 512), (16, 100, 200), (1, 7, 33)],
)
def test_sketch_similarity_sweep(M, Nf, K):
    k = jax.random.PRNGKey(M * 31 + Nf)
    a = jax.random.normal(k, (M, K))
    b = jax.random.normal(jax.random.fold_in(k, 1), (Nf, K))
    got = sketch_similarity(a, b, interpret=True)
    assert got.shape == (M, Nf) and got.dtype == jnp.float32
    np.testing.assert_allclose(got, ref.sketch_similarity_ref(a, b),
                               rtol=1e-4, atol=1e-4)


def test_sketch_similarity_blocked_contraction():
    """K larger than block_k exercises the accumulating k-grid (the dense-
    defense path where the contraction axis is the full model dim)."""
    k = jax.random.PRNGKey(5)
    a = jax.random.normal(k, (24, 1000))
    b = jax.random.normal(jax.random.fold_in(k, 1), (96, 1000))
    got = sketch_similarity(a, b, interpret=True, block_n=128, block_k=256)
    np.testing.assert_allclose(got, ref.sketch_similarity_ref(a, b),
                               rtol=1e-4, atol=1e-4)


def test_sketch_similarity_padded_tails_do_not_leak():
    """N and K both off the block grid: zero padding must be sliced away."""
    k = jax.random.PRNGKey(6)
    a = jax.random.normal(k, (5, 300))
    b = jax.random.normal(jax.random.fold_in(k, 1), (130, 300))
    got = sketch_similarity(a, b, interpret=True, block_n=128, block_k=128)
    assert got.shape == (5, 130)
    np.testing.assert_allclose(got, ref.sketch_similarity_ref(a, b),
                               rtol=1e-4, atol=1e-4)


def test_sketch_similarity_vmem_fit():
    """Block fitting keeps the three fp32 tiles inside the VMEM budget even
    for wide shard blocks."""
    from repro.kernels.defense_sim import VMEM_BUDGET_BYTES, _fit_blocks

    for m in (8, 128, 512):
        bn, bk = _fit_blocks(m, 512, 512)
        assert bn >= 128 and bk >= 128
        assert 4 * (m * bk + bn * bk + m * bn) <= VMEM_BUDGET_BYTES or (
            bn == 128 and bk == 128
        )


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize(
    "B,S,H,hd,window,bq,bk",
    [
        (2, 256, 4, 64, 0, 64, 64),
        (1, 256, 2, 128, 64, 64, 64),
        (2, 128, 3, 32, 0, 32, 64),
        (1, 512, 1, 64, 128, 128, 128),
        (3, 128, 2, 64, 16, 32, 32),
    ],
)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(B, S, H, hd, window, bq, bk, dtype):
    ks = jax.random.split(jax.random.PRNGKey(S + H), 3)
    q, k, v = (jax.random.normal(kk, (B, S, H, hd), dtype) for kk in ks)
    got = flash_attention(q, k, v, causal=True, window=window,
                          interpret=True, block_q=bq, block_k=bk)
    want = ref.flash_attention_ref(q, k, v, causal=True, window=window)
    tol = 2e-3 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=tol, atol=tol,
    )


def test_flash_attention_first_row_is_v0():
    """Causal row 0 attends only to position 0."""
    B, S, H, hd = 1, 64, 1, 32
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k, v = (jax.random.normal(kk, (B, S, H, hd)) for kk in ks)
    out = flash_attention(q, k, v, interpret=True, block_q=32, block_k=32)
    np.testing.assert_allclose(out[0, 0], v[0, 0], rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# ssm scan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize(
    "B,S,nh,hd,st_,chunk,hb",
    [
        (2, 64, 8, 32, 16, 16, 4),
        (1, 128, 4, 64, 64, 32, 4),
        (2, 96, 2, 16, 8, 32, 2),
        (1, 256, 8, 32, 32, 64, 8),
    ],
)
def test_ssm_scan_sweep(B, S, nh, hd, st_, chunk, hb):
    ks = jax.random.split(jax.random.PRNGKey(S * nh), 4)
    xd = jax.random.normal(ks[0], (B, S, nh, hd)) * 0.5
    logdecay = -jax.nn.softplus(jax.random.normal(ks[1], (B, S, nh)))
    Bc = jax.random.normal(ks[2], (B, S, st_)) * 0.5
    Cc = jax.random.normal(ks[3], (B, S, st_)) * 0.5
    got = ssm_scan(xd, logdecay, Bc, Cc, chunk=chunk, head_block=hb, interpret=True)
    want = ref.ssm_scan_ref(xd, logdecay, Bc, Cc)
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)


def test_ssm_scan_matches_model_path():
    """Kernel == the model's XLA ssd_chunked == exact recurrence."""
    from repro.models.ssm import ssd_chunked

    ks = jax.random.split(jax.random.PRNGKey(5), 4)
    B, S, nh, hd, st_ = 2, 64, 4, 32, 16
    xd = jax.random.normal(ks[0], (B, S, nh, hd)) * 0.5
    logdecay = -jax.nn.softplus(jax.random.normal(ks[1], (B, S, nh)))
    Bc = jax.random.normal(ks[2], (B, S, st_)) * 0.5
    Cc = jax.random.normal(ks[3], (B, S, st_)) * 0.5
    want = ref.ssm_scan_ref(xd, logdecay, Bc, Cc)
    kern = ssm_scan(xd, logdecay, Bc, Cc, chunk=16, head_block=4, interpret=True)
    xla, _ = ssd_chunked(xd, logdecay, Bc, Cc, 16)
    np.testing.assert_allclose(kern, want, rtol=2e-3, atol=2e-3)
    np.testing.assert_allclose(xla, want, rtol=2e-3, atol=2e-3)


def test_ssm_decay_zero_state_passthrough():
    """With logdecay = -inf (full reset) y_t depends only on step t."""
    B, S, nh, hd, st_ = 1, 32, 2, 16, 8
    ks = jax.random.split(jax.random.PRNGKey(9), 3)
    xd = jax.random.normal(ks[0], (B, S, nh, hd))
    Bc = jax.random.normal(ks[1], (B, S, st_))
    Cc = jax.random.normal(ks[2], (B, S, st_))
    logdecay = jnp.full((B, S, nh), -100.0)
    got = ssm_scan(xd, logdecay, Bc, Cc, chunk=8, head_block=2, interpret=True)
    want = jnp.einsum("bls,bls->bl", Cc, Bc)[..., None, None] * xd
    np.testing.assert_allclose(got, want, rtol=1e-3, atol=1e-3)


# ---------------------------------------------------------------------------
# compression pack / unpack (kernels/compress.py)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("bits", [4, 8])
@pytest.mark.parametrize("N,D", [(12, 25450), (3, 97), (1, 1), (7, 1000),
                                 (5, 2), (2, 255)])
@pytest.mark.parametrize("dtype", [jnp.int32, jnp.uint8, jnp.int16])
def test_pack_unpack_bit_equal_to_ref(bits, N, D, dtype):
    """Pack/unpack kernels are BIT-equal to the pure-jnp oracles across
    code dtypes and odd D (non-multiples of the pack tile), and unpack
    inverts pack exactly."""
    codes = jax.random.randint(
        jax.random.PRNGKey(N * 131 + D), (N, D), 0, 2**bits
    ).astype(dtype)
    want = ref.pack_codes_ref(codes, bits=bits)
    got = pack_codes(codes, bits=bits, interpret=True)
    assert got.dtype == jnp.uint8
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    back = unpack_codes(got, bits=bits, dim=D, interpret=True)
    np.testing.assert_array_equal(
        np.asarray(back),
        np.asarray(ref.unpack_codes_ref(want, bits=bits, dim=D)),
    )
    np.testing.assert_array_equal(np.asarray(back),
                                  np.asarray(codes, np.int32))


def test_pack_small_block_padded_tail():
    """D far from the lane tile: the zero-padded tail must not leak into
    the packed bytes (block_d forced small so padding actually happens)."""
    codes = jax.random.randint(jax.random.PRNGKey(0), (4, 333), 0, 16)
    got = pack_codes(codes, bits=4, interpret=True, block_d=128)
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(ref.pack_codes_ref(codes, bits=4))
    )
