"""Pallas TPU kernels: quantization-code pack/unpack + top-k scatter decode.

The uplink-compression hot ops (``core/compress.py``) are elementwise bit
twiddling and a sparse->dense scatter — both memory-bound, both tiled over
the parameter axis D in lane-aligned VMEM blocks like ``fedavg_agg``:

  ``pack_codes``   -- offset-encoded int codes -> packed uint8.  bits=8 is
                      a cast (no kernel needed); bits=4 ORs two nibble
                      planes per byte.  The 4-bit layout is HALF-SPLIT
                      (byte j = code[j] | code[P+j] << 4, P = ceil(D/2)),
                      so each grid step reads two aligned (N, block) tiles
                      instead of doing a cross-lane even/odd deinterleave.
  ``unpack_codes`` -- the inverse: one packed tile -> low/high nibble
                      planes, reassembled (and sliced to D) outside.
  ``topk_decode``  -- (N, k) value/index pairs -> dense (N, D) fp32.  Each
                      grid step owns an (N, block) column window and folds
                      over k with a compare-and-accumulate (duplicate
                      indices ADD, matching the ref scatter).

Pack/unpack kernels compute in int32 (TPU-native) and cast to uint8 at the
boundary; bit-equality with ``kernels/ref.py`` is pinned by
``tests/test_kernels.py`` across dtypes and odd (non-tile-multiple) D.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK_D = 1024  # lane-aligned (1024 = 8 * 128)
LANES = 128
VMEM_BUDGET_BYTES = 4 * 1024 * 1024


def _fit_block(n: int, block_d: int) -> int:
    """Shrink ``block_d`` (multiple of 128, floor 128) until the int32
    (N, block) tiles fit the VMEM budget."""
    cap = VMEM_BUDGET_BYTES // (4 * n)
    return max(128, min(block_d, cap // 128 * 128))


def _pack4_kernel(lo_ref, hi_ref, o_ref):
    # lo/hi: (N, BLOCK) int32 nibble planes -> o: (N, BLOCK) packed bytes
    o_ref[...] = lo_ref[...] | (hi_ref[...] << 4)


def _unpack4_kernel(p_ref, lo_ref, hi_ref):
    p = p_ref[...]
    lo_ref[...] = p & 0xF
    hi_ref[...] = (p >> 4) & 0xF


@functools.partial(jax.jit, static_argnames=("bits", "interpret", "block_d"))
def pack_codes(codes, *, bits: int, interpret: bool = False,
               block_d: int = BLOCK_D):
    """codes: (N, D) int in [0, 2^bits) -> packed (N, P) uint8 with
    P = ceil(D * bits / 8), bit-equal to ``ref.pack_codes_ref``."""
    if bits == 8:
        return codes.astype(jnp.uint8)  # one code per byte: a pure cast
    N, D = codes.shape
    P = (D + 1) // 2
    c = jnp.pad(codes.astype(jnp.int32), ((0, 0), (0, 2 * P - D)))
    lo, hi = c[:, :P], c[:, P:]
    block_d = _fit_block(N, block_d)
    pad = (-P) % block_d
    if pad:
        lo = jnp.pad(lo, ((0, 0), (0, pad)))
        hi = jnp.pad(hi, ((0, 0), (0, pad)))
    Pp = P + pad
    out = pl.pallas_call(
        _pack4_kernel,
        grid=(Pp // block_d,),
        in_specs=[
            pl.BlockSpec((N, block_d), lambda i: (0, i)),
            pl.BlockSpec((N, block_d), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((N, block_d), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((N, Pp), jnp.int32),
        interpret=interpret,
    )(lo, hi)
    return out[:, :P].astype(jnp.uint8)


@functools.partial(
    jax.jit, static_argnames=("bits", "dim", "interpret", "block_d")
)
def unpack_codes(packed, *, bits: int, dim: int, interpret: bool = False,
                 block_d: int = BLOCK_D):
    """packed: (N, P) uint8 -> (N, dim) int32 codes, bit-equal to
    ``ref.unpack_codes_ref``."""
    if bits == 8:
        return packed[:, :dim].astype(jnp.int32)
    N, P = packed.shape
    block_d = _fit_block(N, block_d)
    pad = (-P) % block_d
    p32 = packed.astype(jnp.int32)
    if pad:
        p32 = jnp.pad(p32, ((0, 0), (0, pad)))
    Pp = P + pad
    lo, hi = pl.pallas_call(
        _unpack4_kernel,
        grid=(Pp // block_d,),
        in_specs=[pl.BlockSpec((N, block_d), lambda i: (0, i))],
        out_specs=[
            pl.BlockSpec((N, block_d), lambda i: (0, i)),
            pl.BlockSpec((N, block_d), lambda i: (0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((N, Pp), jnp.int32),
            jax.ShapeDtypeStruct((N, Pp), jnp.int32),
        ],
        interpret=interpret,
    )(p32)
    return jnp.concatenate([lo[:, :P], hi[:, :P]], axis=-1)[:, :dim]


def _topk_kernel(v_ref, i_ref, o_ref, *, block_d: int):
    # v/i: (bn, kp) value/index rows; o: (bn, block_d) — column window
    # [j*block_d, (j+1)*block_d).  k is walked in lane-aligned 128-wide
    # chunks loaded straight from the refs; each chunk's pairs fold in
    # order through static lane slices, so no dynamic value slicing is
    # needed and the accumulation order is exactly t = 0, 1, ..., k-1.
    j = pl.program_id(1)
    bn, kp = v_ref.shape
    cols = j * block_d + jax.lax.broadcasted_iota(
        jnp.int32, (bn, block_d), 1
    )

    def chunk(c, acc):
        start = pl.multiple_of(c * LANES, LANES)
        vc = v_ref[:, pl.ds(start, LANES)]
        ic = i_ref[:, pl.ds(start, LANES)]
        for lane in range(LANES):
            vt = vc[:, lane:lane + 1]
            it = ic[:, lane:lane + 1]
            acc = acc + vt * (it == cols).astype(jnp.float32)
        return acc

    o_ref[...] = jax.lax.fori_loop(
        0, kp // LANES, chunk, jnp.zeros((bn, block_d), jnp.float32)
    )


def _topk_blocks(n: int, kp: int, block_d: int) -> tuple[int, int]:
    """Row block (multiple of 8, or all of n when n < 8) and column window
    of ``topk_decode``: the double-buffered (bn, kp) value and index tiles
    plus the (bn, block_d) output tile stay within the VMEM budget."""
    bd = max(LANES, block_d // LANES * LANES)
    per_row = 4 * 2 * (2 * kp + bd)
    bn = max(8, VMEM_BUDGET_BYTES // per_row // 8 * 8)
    return (n if n <= bn else bn), bd


@functools.partial(jax.jit, static_argnames=("dim", "interpret", "block_d"))
def topk_decode(vals, idx, dim: int, *, interpret: bool = False,
                block_d: int = BLOCK_D):
    """vals, idx: (N, k) -> dense (N, dim) float32; duplicate indices
    accumulate (scatter-add), matching ``ref.topk_decode_ref``.  k == 0
    (nothing kept / all rows masked upstream) short-circuits to zeros.

    k is padded to a lane multiple with (value 0, index -1) pairs, which
    match no column; N is tiled in row blocks so the value/index tiles
    fit VMEM at fleet-scale N."""
    N, k = vals.shape
    if k == 0:
        return jnp.zeros((N, dim), jnp.float32)
    kp = -(-k // LANES) * LANES
    bn, block_d = _topk_blocks(N, kp, block_d)
    pad_n, pad_d = (-N) % bn, (-dim) % block_d
    vals = jnp.pad(vals.astype(jnp.float32), ((0, pad_n), (0, kp - k)))
    idx = jnp.pad(idx.astype(jnp.int32), ((0, pad_n), (0, kp - k)),
                  constant_values=-1)
    Np, Dp = N + pad_n, dim + pad_d
    out = pl.pallas_call(
        functools.partial(_topk_kernel, block_d=block_d),
        grid=(Np // bn, Dp // block_d),
        in_specs=[
            pl.BlockSpec((bn, kp), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, kp), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bn, block_d), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Np, Dp), jnp.float32),
        interpret=interpret,
    )(vals, idx)
    return out[:N, :dim]
