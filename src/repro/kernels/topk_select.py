"""Pallas TPU kernel: the exact top-k threshold of each row, by selection.

The top-k codec (``core/compress.py``) keeps, in each row of ``v``, the
``k`` coordinates of largest ``|v|``, ties broken toward the lower column
as ``lax.top_k`` breaks them.  That set is fixed by two integers a row:

  ``thr``  -- the k-th largest int32 key ``bitcast(|v|)``.  The int32 view
              of a non-negative float orders exactly as the float total
              order does (+0 at 0, NaN above +inf), so keys compare as
              ``top_k`` compares magnitudes;
  ``last`` -- the column of the last kept tie at ``thr``: keys above
              ``thr`` are all kept, and ``r = k - count(key > thr)`` of
              the ties, the leftmost ones.

Both come from bisection, with nothing sorted: ``thr`` is the largest t
with ``count(key >= t) >= k`` (31 steps over bits 30..0), ``last`` the
smallest column L with ``count(key == thr & col <= L) >= r`` (one step
per bit of a column index).  Every step is a count over the row.

The kernel holds a block of ``ROWS`` whole rows in VMEM (the input block,
double-buffered, and an int32 key scratch), so all the counting sweeps
read VMEM and HBM is read once.  ``topk_select_fits_vmem`` says whether a
width fits; ``kernels/ref.py::topk_select_ref`` is the same bisection in
``jax.numpy``, the oracle and the off-TPU path.  Columns past D (the
block's lane padding, whatever it holds) take key -1, which no count
sees; rows past the array's end compute garbage that is never written.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.local_sgd import VMEM_LIMIT_BYTES

ROWS = 8  # f32 sublanes: one vreg holds 8 rows x 128 columns
KEY_BITS = 31  # a non-negative int32 key


def _chunk(dim: int) -> int:
    """Columns a counting step reads at once: up to eight vregs a row."""
    return min(1024, -(-dim // 128) * 128)


def _padded(dim: int) -> int:
    ch = _chunk(dim)
    return -(-dim // ch) * ch


def col_bits(dim: int) -> int:
    """Bisection steps over a column index in [0, dim)."""
    return (dim - 1).bit_length()


def topk_select_fits_vmem(dim: int, limit: int = VMEM_LIMIT_BYTES) -> bool:
    """Whether a ``ROWS``-row block of width ``dim`` fits the scoped VMEM
    limit the kernel compiles under: the double-buffered f32 input block
    and the int32 key scratch (``3 * ROWS * padded width * 4`` bytes)."""
    return 3 * ROWS * _padded(dim) * 4 <= limit


def _kernel(v_ref, thr_ref, last_ref, key_ref, *, k, dim, ch):
    n_chunks = key_ref.shape[1] // ch
    lane = jax.lax.broadcasted_iota(jnp.int32, (ROWS, ch), 1)

    def fill(j, carry):
        off = pl.multiple_of(j * ch, ch)
        bits = jax.lax.bitcast_convert_type(v_ref[:, pl.ds(off, ch)],
                                            jnp.int32)
        key_ref[:, pl.ds(off, ch)] = jnp.where(off + lane < dim,
                                               bits & 0x7FFFFFFF, -1)
        return carry

    jax.lax.fori_loop(0, n_chunks, fill, 0)

    def count(pred):
        """``(ROWS, 1)`` counts of ``pred(key, col)`` over each row."""
        def body(j, acc):
            off = pl.multiple_of(j * ch, ch)
            hit = pred(key_ref[:, pl.ds(off, ch)], off + lane)
            return acc + hit.astype(jnp.int32)

        acc = jax.lax.fori_loop(0, n_chunks, body,
                                jnp.zeros((ROWS, ch), jnp.int32))
        return jnp.sum(acc, axis=1, keepdims=True)

    def key_step(b, thr):
        cand = thr | jnp.left_shift(1, KEY_BITS - 1 - b)
        n = count(lambda key, col: key >= cand)
        return jnp.where(n >= k, cand, thr)

    thr = jax.lax.fori_loop(0, KEY_BITS, key_step,
                            jnp.zeros((ROWS, 1), jnp.int32))
    r = k - count(lambda key, col: key > thr)
    nbits = col_bits(dim)

    def col_step(b, last):
        cand = last | jnp.left_shift(1, nbits - 1 - b)
        n = count(lambda key, col: (key == thr) & (col < cand))
        return jnp.where(n < r, cand, last)

    thr_ref[...] = thr
    last_ref[...] = jax.lax.fori_loop(0, nbits, col_step,
                                      jnp.zeros((ROWS, 1), jnp.int32))


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def topk_select(v, *, k: int, interpret: bool = False):
    """``v`` (n, D) f32 -> ``(thr, last)``, each (n, 1) int32: the k-th
    largest key ``bitcast_int32(|v|)`` of each row, and the column of the
    last tie at it that ``lax.top_k(|v|, k)`` keeps.  Equal to
    ``ref.topk_select_ref``."""
    n, dim = v.shape
    ch = _chunk(dim)
    width = _padded(dim)
    kernel = functools.partial(_kernel, k=k, dim=dim, ch=ch)
    row = pl.BlockSpec((ROWS, 1), lambda i: (i, 0))
    return pl.pallas_call(
        kernel,
        grid=(pl.cdiv(n, ROWS),),
        in_specs=[pl.BlockSpec((ROWS, width), lambda i: (i, 0))],
        out_specs=[row, row],
        out_shape=[jax.ShapeDtypeStruct((n, 1), jnp.int32)] * 2,
        scratch_shapes=[pltpu.VMEM((ROWS, width), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
        ),
        interpret=interpret,
    )(v.astype(jnp.float32))
