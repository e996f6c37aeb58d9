"""Pallas TPU kernel: fused masked local SGD for the FedAR client MLP.

ClientUpdate (Algorithm 2 lines 16-21) is the engine's FLOP-dominant op:
every selected client runs E epochs of batch SGD on its local shard.  The
XLA path vmaps a ``lax.scan`` of ``jax.grad`` steps — each batch step
round-trips the full parameter set through HBM.  This kernel fuses the
whole per-client loop (epochs x batches of forward + backward + SGD update)
into ONE ``pallas_call``: the grid walks the client rows of a (bucketed)
cohort block, each grid step streams that client's sample slab HBM->VMEM
once, keeps the evolving parameters resident in the output VMEM tiles, and
iterates every batch against them — zero parameter traffic between steps.

Masked tiles are skipped: a batch whose validity-mask count is zero (the
pad-to-bucket tail of a packed shard, or a dummy mesh-fill row) is an exact
no-op on the XLA path (the masked loss renormalizes to zero gradient), so
``pl.when`` guards the entire batch body and the kernel pays nothing for
padding — the residual <=2x pad-to-bucket waste of the packed layout
becomes pure skipped tiles here.

Two entry points share the batch body:

``local_sgd_fused``        — one rectangular client block (R, n, I); the
                             grid walks clients, each grid step keeps the
                             whole sample slab in VMEM and ``fori_loop``s
                             its epochs x batches.
``local_sgd_fused_ragged`` — the WHOLE bucketed packed layout in ONE
                             launch: clients of every width bucket are
                             flattened to a single (T, B, I) batch-tile
                             buffer, and a ``PrefetchScalarGridSpec`` grid
                             (client, epoch, batch) streams each client's
                             tiles through scalar-prefetched per-client
                             tile offsets / batch counts.  Ragged widths
                             become skipped grid steps instead of separate
                             ``pallas_call`` dispatches, so the per-bucket
                             launch + gather overhead of the packed layout
                             disappears.

The backward pass is written out by hand (softmax cross-entropy through the
Table II per-robot hidden activation, ReLU or Softmax) and matches
``jax.grad`` of ``models.mnist.mnist_loss`` — pinned against the pure-jnp
oracle ``kernels.ref.local_sgd_ref`` and ``models.mnist.local_sgd`` in the
kernel tests.  Routed via ``FedConfig.sgd_impl`` (auto = kernel on TPU,
XLA vmap elsewhere).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Scoped-VMEM limit handed to the TPU compiler for the fused kernels, and the
# budget ``fused_fits_vmem`` checks a block against.  v5e has 128 MiB of
# VMEM per core; 32 MiB admits the paper's 784-128-10 MLP up to 2,760 samples
# per client at B=20.  ``tests/test_tpu_compile.py`` compiles the widest width this
# admits for a described v5e, so the estimate below is held to what the
# compiler accepts.
VMEM_LIMIT_BYTES = 32 * 1024 * 1024


def _tile_bytes(rows: int, cols: int) -> int:
    """fp32/int32 bytes of a (rows, cols) VMEM tile after (8, 128) padding."""
    return 4 * (-(-rows // 8) * 8) * (-(-cols // 128) * 128)


def fused_fits_vmem(n: int, input_dim: int, hidden: int, classes: int,
                    batch: int = 20, limit: int = VMEM_LIMIT_BYTES) -> bool:
    """Whether one client's ``local_sgd_fused`` grid step — n samples in
    batches of ``batch`` — fits the scoped VMEM limit the kernel compiles
    under.  The engine decides its local-SGD route from this, host-side,
    before tracing (``FedAREngine.sgd_route``).

    The padded footprint counted: the double-buffered (nb, B, I) sample
    slab and its (nb, B, 1) label / mask columns, the double-buffered
    in/out parameter tiles, and the batch body's parameter-sized
    temporaries (loaded weights and gradients)."""
    nb = -(-n // batch)
    slab = nb * (_tile_bytes(batch, input_dim) + 2 * _tile_bytes(batch, 1))
    params = (
        _tile_bytes(input_dim, hidden) + _tile_bytes(1, hidden)
        + _tile_bytes(hidden, classes) + _tile_bytes(1, classes)
    )
    temps = 2 * (_tile_bytes(input_dim, hidden) + _tile_bytes(hidden, classes))
    return 2 * slab + 4 * params + temps <= limit


def _compiler_params():
    return pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT_BYTES)


def _batch_body(xb, yb, mb, is_soft, w1o, b1o, w2o, b2o, *, lr):
    """One masked SGD step against the params resident in the output VMEM
    tiles (shared by the rectangular and the ragged-grid kernels).
    ``xb`` (B, I), ``yb`` (B, 1) int labels, ``mb`` (B, 1) float validity.
    An all-padding batch is an exact no-op (the masked loss renormalizes to
    zero gradient), so ``pl.when`` skips it entirely."""
    cnt = jnp.sum(mb)

    @pl.when(cnt > 0.0)
    def _():
        w1, b1 = w1o[...], b1o[...]
        w2, b2 = w2o[...], b2o[...]
        hpre = jax.lax.dot_general(
            xb, w1, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) + b1
        h = jnp.where(
            is_soft, jax.nn.softmax(hpre, axis=-1),
            jnp.maximum(hpre, 0.0),
        )
        logits = jax.lax.dot_general(
            h, w2, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) + b2
        # d(masked CE)/d(logits) = (softmax - onehot) * m / sum(m)
        p = jax.nn.softmax(logits, axis=-1)
        col = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
        onehot = (col == yb).astype(jnp.float32)
        gl = (p - onehot) * (mb / jnp.maximum(cnt, 1.0))
        dw2 = jax.lax.dot_general(
            h, gl, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        db2 = jnp.sum(gl, axis=0, keepdims=True)
        dh = jax.lax.dot_general(
            gl, w2, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        # back through the Table II hidden activation
        dsoft = h * (dh - jnp.sum(dh * h, axis=-1, keepdims=True))
        drelu = dh * (hpre > 0.0)
        dhp = jnp.where(is_soft, dsoft, drelu)
        dw1 = jax.lax.dot_general(
            xb, dhp, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        db1 = jnp.sum(dhp, axis=0, keepdims=True)
        w1o[...] = w1 - lr * dw1
        b1o[...] = b1 - lr * db1
        w2o[...] = w2 - lr * dw2
        b2o[...] = b2 - lr * db2


def _init_params(w1_ref, b1_ref, w2_ref, b2_ref, w1o, b1o, w2o, b2o):
    w1o[...] = w1_ref[...]
    b1o[...] = b1_ref[...]
    w2o[...] = w2_ref[...]
    b2o[...] = b2_ref[...]


def _sgd_kernel(act_ref, x_ref, y_ref, m_ref, w1_ref, b1_ref, w2_ref, b2_ref,
                w1o, b1o, w2o, b2o, *, lr, nb, epochs):
    # one grid step == one client: params live in the output VMEM tiles and
    # are updated in place across every batch of every epoch.  Batches sit
    # on the slab's leading (untiled) axis, so each step's batch is an
    # aligned whole-tile read.
    _init_params(w1_ref, b1_ref, w2_ref, b2_ref, w1o, b1o, w2o, b2o)
    is_soft = act_ref[pl.program_id(0)] == 1

    def step(t, carry):
        b = jax.lax.rem(t, nb)
        _batch_body(x_ref[b], y_ref[b], m_ref[b], is_soft,
                    w1o, b1o, w2o, b2o, lr=lr)
        return carry

    jax.lax.fori_loop(0, epochs * nb, step, 0)


def _param_in_specs(inp, hid, classes):
    """Full-array blocks of the shared global params (same tile for every
    grid step, whatever the grid's index-map arity)."""
    def zero(*_):
        return (0, 0)

    return [
        pl.BlockSpec((inp, hid), zero),
        pl.BlockSpec((1, hid), zero),
        pl.BlockSpec((hid, classes), zero),
        pl.BlockSpec((1, classes), zero),
    ]


def _param_out(R, inp, hid, classes):
    """Per-client post-SGD param outputs, indexed by the grid's leading
    (client) axis: the client axis is squeezed (``None``) so every block's
    last two dims are the full array dims; the biases carry a unit middle
    axis for the same reason."""
    def client(i, *_):
        return (i, 0, 0)

    specs = [
        pl.BlockSpec((None, inp, hid), client),
        pl.BlockSpec((None, 1, hid), client),
        pl.BlockSpec((None, hid, classes), client),
        pl.BlockSpec((None, 1, classes), client),
    ]
    shapes = [
        jax.ShapeDtypeStruct((R, inp, hid), jnp.float32),
        jax.ShapeDtypeStruct((R, 1, hid), jnp.float32),
        jax.ShapeDtypeStruct((R, hid, classes), jnp.float32),
        jax.ShapeDtypeStruct((R, 1, classes), jnp.float32),
    ]
    return specs, shapes


def _params_args(w1, b1, w2, b2):
    hid, classes = w1.shape[1], w2.shape[1]
    return (
        w1.astype(jnp.float32),
        b1.astype(jnp.float32).reshape(1, hid),
        w2.astype(jnp.float32),
        b2.astype(jnp.float32).reshape(1, classes),
    )


def _unpack_outs(outs, R):
    return {"w1": outs[0], "b1": outs[1].reshape(R, -1), "w2": outs[2],
            "b2": outs[3].reshape(R, -1)}


@functools.partial(
    jax.jit, static_argnames=("lr", "batch_size", "epochs", "interpret")
)
def local_sgd_fused(w1, b1, w2, b2, x, y, act, mask, *, lr: float,
                    batch_size: int, epochs: int, interpret: bool = False):
    """Fused local SGD over a block of clients.

    w1 (I, H), b1 (H,), w2 (H, C), b2 (C,): the shared global model.
    x (R, n, I) float; y (R, n) int; act (R,) int (0=relu, 1=softmax);
    mask (R, n) bool/float validity (padding contributes zero gradient,
    all-padding batches are skipped tiles).

    Returns ``{"w1": (R, I, H), "b1": (R, H), "w2": (R, H, C),
    "b2": (R, C)}`` — each client's post-SGD parameters, fp32.  The sample
    axis is zero-padded up to a whole number of batches (mask-False, so the
    tail never trains), matching the masked XLA path's ceil batching."""
    R, n, inp = x.shape
    hid = w1.shape[1]
    classes = w2.shape[1]
    nb = -(-n // batch_size)  # ceil: never drop real samples
    pad = nb * batch_size - n
    mask = mask.astype(jnp.float32)
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        y = jnp.pad(y, ((0, 0), (0, pad)))
        mask = jnp.pad(mask, ((0, 0), (0, pad)))
    kernel = functools.partial(_sgd_kernel, lr=lr, nb=nb, epochs=epochs)
    out_specs, out_shape = _param_out(R, inp, hid, classes)
    slab = lambda i, *_: (i, 0, 0, 0)  # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(R,),
        in_specs=[
            pl.BlockSpec((None, nb, batch_size, inp), slab),
            pl.BlockSpec((None, nb, batch_size, 1), slab),
            pl.BlockSpec((None, nb, batch_size, 1), slab),
            *_param_in_specs(inp, hid, classes),
        ],
        out_specs=out_specs,
    )
    outs = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        compiler_params=_compiler_params(),
        interpret=interpret,
    )(
        act.astype(jnp.int32),
        x.astype(jnp.float32).reshape(R, nb, batch_size, inp),
        y.astype(jnp.int32).reshape(R, nb, batch_size, 1),
        mask.reshape(R, nb, batch_size, 1),
        *_params_args(w1, b1, w2, b2),
    )
    return _unpack_outs(outs, R)


def _ragged_kernel(act_ref, nb_ref, off_ref, x_ref, y_ref, m_ref,
                   w1_ref, b1_ref, w2_ref, b2_ref,
                   w1o, b1o, w2o, b2o, *, lr):
    # grid = (client, epoch, batch): the output param tiles index by client
    # only, so they stay resident in VMEM across a client's whole
    # epochs x batches walk and spill back to HBM once per client
    i, e, b = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when((e == 0) & (b == 0))
    def _():
        _init_params(w1_ref, b1_ref, w2_ref, b2_ref, w1o, b1o, w2o, b2o)

    # ragged skip: grid batch steps past this client's own batch count are
    # no-ops (the index map clamps their tile fetch to a valid slot)
    @pl.when(b < nb_ref[i])
    def _():
        _batch_body(
            x_ref[...], y_ref[...], m_ref[...], act_ref[i] == 1,
            w1o, b1o, w2o, b2o, lr=lr,
        )


@functools.partial(
    jax.jit, static_argnames=("lr", "epochs", "nb_max", "interpret")
)
def local_sgd_fused_ragged(w1, b1, w2, b2, xt, yt, mt, act, nb, off, *,
                           lr: float, epochs: int, nb_max: int,
                           interpret: bool = False):
    """The WHOLE ragged bucketed layout in ONE ``pallas_call``.

    The caller flattens every width bucket into one batch-tile buffer:
    ``xt`` (T, B, I) float, ``yt`` (T, B) int, ``mt`` (T, B) float validity
    — client r's tiles are ``xt[off[r] : off[r] + nb[r]]``.  ``act`` (R,)
    int per-client activation id, ``nb`` (R,) int32 per-client batch
    count, ``off`` (R,) int32 per-client tile offset (all scalar-prefetched
    so the grid's index maps can address each client's slab); ``nb_max``
    is the static grid bound ``max(nb)``.

    Grid (R, epochs, nb_max) — batch fastest, so each client's SGD walk is
    sequential while params stay resident in its output VMEM tiles; steps
    with ``b >= nb[r]`` (a narrower client's tail of the widest bucket's
    schedule) skip via ``pl.when``, which is how a SINGLE launch covers
    every bucket width with zero per-bucket dispatch.

    Returns ``{"w1": (R, I, H), "b1": (R, H), "w2": (R, H, C),
    "b2": (R, C)}`` — bit-identical to running ``local_sgd_fused`` per
    bucket."""
    R = act.shape[0]
    T, batch, inp = xt.shape
    hid = w1.shape[1]
    classes = w2.shape[1]
    kernel = functools.partial(_ragged_kernel, lr=lr)

    def tile(i, e, b, act, nb, off):
        return (off[i] + jnp.minimum(b, nb[i] - 1), 0, 0)

    out_specs, out_shape = _param_out(R, inp, hid, classes)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(R, epochs, nb_max),
        in_specs=[
            pl.BlockSpec((None, batch, inp), tile),
            pl.BlockSpec((None, batch, 1), tile),
            pl.BlockSpec((None, batch, 1), tile),
            *_param_in_specs(inp, hid, classes),
        ],
        out_specs=out_specs,
    )
    outs = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=out_shape,
        compiler_params=_compiler_params(),
        interpret=interpret,
    )(
        act.astype(jnp.int32),
        nb.astype(jnp.int32),
        off.astype(jnp.int32),
        xt.astype(jnp.float32),
        yt.astype(jnp.int32).reshape(T, batch, 1),
        mt.astype(jnp.float32).reshape(T, batch, 1),
        *_params_args(w1, b1, w2, b2),
    )
    return _unpack_outs(outs, R)
