"""Pallas TPU kernel: trust-weighted, staleness-decayed federated aggregation.

The FedAR server's hot op — ``out[d] = sum_n w[n] * s(tau[n]) * deltas[n, d]``
over stacked client deltas — is a memory-bound streaming reduction (arithmetic
intensity ~2 FLOPs / 4 bytes).  ``s(tau) = (1 + tau)^-0.5`` is the FedAsync
poly staleness discount applied to buffered-async deliveries; folding it into
the kernel keeps the reduction single-pass (no host-side weight pre-multiply,
no second sweep over the (N, D) slab).

Tiling: the parameter axis D is blocked into lane-aligned VMEM tiles; each
grid step streams its (N, BLOCK_D) slab HBM->VMEM once and reduces over
clients in fp32.  The block shrinks as the fleet grows so the slab stays
within a fixed VMEM budget (4 MiB):
    N=256  -> BLOCK_D=2048 (2 MiB/step);  N=4096 -> BLOCK_D=256 (4 MiB/step).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK_D = 2048  # lane-aligned (2048 = 16 * 128)
VMEM_BUDGET_BYTES = 4 * 1024 * 1024  # cap on the fp32 (N, block) slab
# scoped VMEM the compiler may use: the double-buffered slab plus the
# (N, 1) weight / staleness columns, which pad to (N, 128) tiles
VMEM_LIMIT_BYTES = 32 * 1024 * 1024


def _fit_block(n: int, block_d: int) -> int:
    """Shrink ``block_d`` (to a multiple of 128, floor 128) until the fp32
    (N, block) slab fits the VMEM budget; large fleets get narrower tiles."""
    cap = VMEM_BUDGET_BYTES // (4 * n)
    return max(128, min(block_d, cap // 128 * 128))


def _agg_kernel(w_ref, s_ref, d_ref, o_ref):
    # w_ref, s_ref: (N, 1) f32; d_ref: (N, BLOCK_D); o_ref: (1, BLOCK_D)
    w = w_ref[...]  # (N, 1) trust/size weights
    s = s_ref[...]  # (N, 1) staleness in rounds (0 = fresh)
    d = d_ref[...].astype(jnp.float32)  # (N, BLOCK_D)
    wd = w * jax.lax.rsqrt(1.0 + s)  # poly staleness decay, fused in-pass
    o_ref[...] = jnp.sum(wd * d, axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret", "block_d"))
def fedavg_agg(
    deltas,
    weights,
    *,
    staleness=None,
    interpret: bool = False,
    block_d: int = BLOCK_D,
):
    """deltas: (N, D) any float dtype; weights: (N,) -> (D,) float32.

    ``staleness``: optional (N,) float — rounds each buffered update waited
    before merging; decayed as ``(1 + tau)^-0.5`` inside the kernel (one
    pass).  ``None`` means every update is fresh (pure trust-weighted sum).

    D is padded to a multiple of ``block_d`` (zero-padded tail contributes
    zeros, then sliced off)."""
    N, D = deltas.shape
    block_d = _fit_block(N, block_d)
    if staleness is None:
        staleness = jnp.zeros((N,), jnp.float32)
    pad = (-D) % block_d
    if pad:
        deltas = jnp.pad(deltas, ((0, 0), (0, pad)))
    Dp = D + pad
    grid = (Dp // block_d,)
    out = pl.pallas_call(
        _agg_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((N, 1), lambda i: (0, 0)),
            pl.BlockSpec((N, 1), lambda i: (0, 0)),
            pl.BlockSpec((N, block_d), lambda i: (0, i)),
        ],
        # a (1, Dp) row, not a (Dp,) vector: a 1-D block must match XLA's
        # 1024-element tiling, which the narrower blocks of big fleets miss
        out_specs=pl.BlockSpec((1, block_d), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, Dp), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES
        ),
        interpret=interpret,
    )(
        weights.astype(jnp.float32)[:, None],
        staleness.astype(jnp.float32)[:, None],
        deltas,
    )
    return out[0, :D]
