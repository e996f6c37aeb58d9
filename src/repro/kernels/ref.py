"""Pure-jnp oracles for every Pallas kernel (the allclose ground truth)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def fedavg_agg_ref(deltas, weights, staleness=None):
    """Trust-weighted (optionally staleness-decayed) server aggregation.
    deltas: (N, D); weights: (N,) -> (D,) float32."""
    w = weights.astype(jnp.float32)
    if staleness is not None:
        w = w * (1.0 + staleness.astype(jnp.float32)) ** -0.5
    return jnp.einsum("n,nd->d", w, deltas.astype(jnp.float32))


def local_sgd_ref(w1, b1, w2, b2, x, y, act, mask, *, lr: float,
                  batch_size: int, epochs: int):
    """One client's masked local SGD (the fused-kernel oracle): E epochs of
    batch SGD via ``jax.grad`` of the masked softmax cross-entropy through
    the Table II hidden activation.  x (n, I), y (n,), mask (n,), act a
    scalar int (0=relu, 1=softmax).  Returns the post-SGD params dict."""

    def loss(params, xb, yb, mb):
        w1, b1, w2, b2 = params
        h = xb @ w1 + b1
        h = jnp.where(
            jnp.asarray(act) == 1, jax.nn.softmax(h, axis=-1),
            jnp.maximum(h, 0.0),
        )
        lg = h @ w2 + b2
        lse = jax.nn.logsumexp(lg, axis=-1)
        gold = jnp.take_along_axis(lg, yb[:, None], axis=-1)[:, 0]
        return jnp.sum((lse - gold) * mb) / jnp.maximum(jnp.sum(mb), 1.0)

    n = x.shape[0]
    nb = -(-n // batch_size)
    pad = nb * batch_size - n
    x = jnp.pad(x.astype(jnp.float32), ((0, pad), (0, 0)))
    y = jnp.pad(y.astype(jnp.int32), ((0, pad),))
    m = jnp.pad(mask.astype(jnp.float32), ((0, pad),))
    params = (
        w1.astype(jnp.float32), b1.astype(jnp.float32),
        w2.astype(jnp.float32), b2.astype(jnp.float32),
    )
    grad = jax.grad(loss)
    for _ in range(epochs):
        for b in range(nb):
            sl = slice(b * batch_size, (b + 1) * batch_size)
            g = grad(params, x[sl], y[sl], m[sl])
            params = tuple(p - lr * gg for p, gg in zip(params, g))
    return {"w1": params[0], "b1": params[1], "w2": params[2],
            "b2": params[3]}


def pack_codes_ref(codes, *, bits: int):
    """Offset-encoded quantization codes (n, D) int in [0, 2^bits) ->
    packed uint8.  bits=8: one code per byte (a cast).  bits=4: the row is
    zero-padded to even width 2P and byte j holds code j in its low nibble
    and code P + j in its high nibble (half-split, not interleaved — the
    layout the Pallas kernel tiles without cross-lane shuffles)."""
    n, d = codes.shape
    c = codes.astype(jnp.int32)
    if bits == 8:
        return c.astype(jnp.uint8)
    p = (d + 1) // 2
    c = jnp.pad(c, ((0, 0), (0, 2 * p - d)))
    return (c[:, :p] | (c[:, p:] << 4)).astype(jnp.uint8)


def unpack_codes_ref(packed, *, bits: int, dim: int):
    """Inverse of ``pack_codes_ref``: (n, P) uint8 -> (n, dim) int32."""
    p32 = packed.astype(jnp.int32)
    if bits == 8:
        return p32[:, :dim]
    full = jnp.concatenate([p32 & 0xF, (p32 >> 4) & 0xF], axis=-1)
    return full[:, :dim]


def topk_decode_ref(vals, idx, dim: int):
    """Sparse (n, k) value/index pairs -> dense (n, dim) float32 via
    scatter-ADD (duplicate indices accumulate): ``TopKCompression.decode``
    of a received payload."""
    n, k = vals.shape
    if k == 0:
        return jnp.zeros((n, dim), jnp.float32)
    out = jnp.zeros((n, dim), jnp.float32)
    rows = jnp.arange(n)[:, None]
    return out.at[rows, idx].add(vals.astype(jnp.float32))


def topk_select_ref(v, k: int):
    """(n, D) -> ``(thr, last)``, each (n, 1) int32: the k-th largest key
    ``bitcast_int32(|v|)`` of each row and the column of the last tie at
    it that ``lax.top_k(|v|, k)`` keeps, by the bisection the
    ``topk_select`` kernel runs (each step a count over the whole row)."""
    key = jax.lax.bitcast_convert_type(jnp.abs(v.astype(jnp.float32)),
                                       jnp.int32)
    n, dim = key.shape
    zero = jnp.zeros((n, 1), jnp.int32)

    def count(hit):
        return jnp.sum(hit, axis=-1, keepdims=True, dtype=jnp.int32)

    def key_step(b, thr):
        cand = thr | jnp.left_shift(1, 30 - b)
        return jnp.where(count(key >= cand) >= k, cand, thr)

    thr = jax.lax.fori_loop(0, 31, key_step, zero)
    r = k - count(key > thr)
    cols = jax.lax.broadcasted_iota(jnp.int32, key.shape, 1)
    nbits = (dim - 1).bit_length()

    def col_step(b, last):
        cand = last | jnp.left_shift(1, nbits - 1 - b)
        return jnp.where(count((key == thr) & (cols < cand)) < r, cand, last)

    return thr, jax.lax.fori_loop(0, nbits, col_step, zero)


def sketch_similarity_ref(unit_loc, unit_full):
    """Defense similarity block: (M, K) @ (N, K).T -> (M, N) float32."""
    return jnp.einsum(
        "mk,nk->mn",
        unit_loc.astype(jnp.float32),
        unit_full.astype(jnp.float32),
    )


def flash_attention_ref(q, k, v, *, causal: bool = True, window: int = 0):
    """q,k,v: (B, S, H, hd) -> (B, S, H, hd).  Full-score reference."""
    B, S, H, hd = q.shape
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32))
    s = s * hd**-0.5
    qi = jnp.arange(S)[:, None]
    ki = jnp.arange(S)[None, :]
    mask = jnp.ones((S, S), bool)
    if causal:
        mask &= ki <= qi
    if window:
        mask &= ki > qi - window
    s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32)).astype(q.dtype)


def ssm_scan_ref(xd, logdecay, Bc, Cc):
    """Sequential (exact) SSD recurrence.
    xd: (B,S,nh,hd) dt-scaled inputs; logdecay: (B,S,nh);
    Bc,Cc: (B,S,st).  Returns y (B,S,nh,hd) float32."""
    B, S, nh, hd = xd.shape
    st = Bc.shape[-1]

    def step(state, inp):
        x_t, l_t, b_t, c_t = inp
        a = jnp.exp(l_t)  # (B,nh)
        upd = jnp.einsum("bs,bnh->bnsh", b_t, x_t)
        state = state * a[:, :, None, None] + upd
        y = jnp.einsum("bs,bnsh->bnh", c_t, state)
        return state, y

    init = jnp.zeros((B, nh, st, hd), jnp.float32)
    xs = (
        xd.transpose(1, 0, 2, 3).astype(jnp.float32),
        logdecay.transpose(1, 0, 2).astype(jnp.float32),
        Bc.transpose(1, 0, 2).astype(jnp.float32),
        Cc.transpose(1, 0, 2).astype(jnp.float32),
    )
    _, ys = jax.lax.scan(step, init, xs)
    return ys.transpose(1, 0, 2, 3)
