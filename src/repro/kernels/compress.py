"""Pallas TPU kernels: quantization-code pack/unpack.

qsgd's uplink hot ops (``core/compress.py``) are elementwise bit
twiddling — memory-bound, tiled over the parameter axis D in lane-aligned
VMEM blocks like ``fedavg_agg``:

  ``pack_codes``   -- offset-encoded int codes -> packed uint8.  bits=8 is
                      a cast (no kernel needed); bits=4 ORs two nibble
                      planes per byte.  The 4-bit layout is HALF-SPLIT
                      (byte j = code[j] | code[P+j] << 4, P = ceil(D/2)),
                      so each grid step reads two aligned (N, block) tiles
                      instead of doing a cross-lane even/odd deinterleave.
  ``unpack_codes`` -- the inverse: one packed tile -> low/high nibble
                      planes, reassembled (and sliced to D) outside.

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
