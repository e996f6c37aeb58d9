"""The paper's client model: a small MLP digit classifier (§IV).

The paper flattens 28x28 images to 784-vectors, trains with local SGD and
SparseCategoricalCrossentropy, and randomly assigns Softmax or ReLU
"activation" per robot (Table II) — we honor that as the hidden activation.
Pure-jnp, vmap-able over a population of clients (each client's params are a
pytree leaf with a leading client axis).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

import numpy as np

from repro.configs.fedar_mnist import MnistConfig
from repro.kernels.local_sgd import (
    fused_fits_vmem,
    local_sgd_fused,
    local_sgd_fused_ragged,
)
from repro.kernels.ops import interpret_mode
from repro.models.client import ClientModel


def init_mnist(key, cfg: MnistConfig):
    k1, k2 = jax.random.split(key)
    s1 = (2.0 / cfg.input_dim) ** 0.5
    s2 = (2.0 / cfg.hidden) ** 0.5
    return {
        "w1": jax.random.normal(k1, (cfg.input_dim, cfg.hidden)) * s1,
        "b1": jnp.zeros((cfg.hidden,)),
        "w2": jax.random.normal(k2, (cfg.hidden, cfg.num_classes)) * s2,
        "b2": jnp.zeros((cfg.num_classes,)),
    }


def mnist_logits(params, x, activation=0):
    """activation: 0 = ReLU, 1 = Softmax (Table II assigns one per robot).
    Accepts a traced int so a fleet can be vmapped with mixed activations."""
    h = x @ params["w1"] + params["b1"]
    act = jnp.asarray(activation)
    h = jnp.where(act == 1, jax.nn.softmax(h, axis=-1), jax.nn.relu(h))
    return h @ params["w2"] + params["b2"]


def mnist_loss(params, x, y, activation=0, sample_mask=None):
    """Cross-entropy; ``sample_mask`` (optional (n,) bool/float) excludes
    padded samples of a ragged client shard — the mean renormalizes over the
    real samples, and a fully-padded batch contributes zero loss (and zero
    gradient) instead of NaN."""
    lg = mnist_logits(params, x, activation)
    lse = jax.nn.logsumexp(lg, axis=-1)
    gold = jnp.take_along_axis(lg, y[:, None], axis=-1)[:, 0]
    per_sample = lse - gold
    if sample_mask is None:
        return jnp.mean(per_sample)
    m = sample_mask.astype(per_sample.dtype)
    return jnp.sum(per_sample * m) / jnp.maximum(jnp.sum(m), 1.0)


def mnist_accuracy(params, x, y, activation=0):
    return jnp.mean(jnp.argmax(mnist_logits(params, x, activation), -1) == y)


def local_sgd(params, x, y, *, lr: float, batch_size: int, epochs: int,
              activation=0, sample_mask=None):
    """ClientUpdate (Algorithm 2 lines 16-21): split local data into batches,
    run E epochs of SGD.  x: (n, 784), y: (n,) — on the dense path n must
    divide by batch (the wrap-padded fleets guarantee it).

    ``sample_mask`` (optional (n,) bool) supports ragged / drifting client
    shards: masked-out samples contribute no gradient, each batch loss
    renormalizes over its real samples, and a batch of pure padding is a
    no-op step.  The masked path rounds the batch count UP, padding the
    tail with mask-False samples, so trailing real samples (or a shard
    smaller than one batch) still train instead of being silently dropped.
    ``None`` keeps the dense code path bit-exact."""
    n = x.shape[0]
    grad_fn = jax.grad(mnist_loss)
    if sample_mask is None:
        nb = n // batch_size
        xb = x[: nb * batch_size].reshape(nb, batch_size, -1)
        yb = y[: nb * batch_size].reshape(nb, batch_size)
        batches = (xb, yb)
    else:
        nb = -(-n // batch_size)  # ceil: never drop real samples
        pad = nb * batch_size - n
        xb = jnp.pad(x, ((0, pad), (0, 0))).reshape(nb, batch_size, -1)
        yb = jnp.pad(y, ((0, pad),)).reshape(nb, batch_size)
        mb = jnp.pad(
            sample_mask.astype(bool), ((0, pad),)
        ).reshape(nb, batch_size)
        batches = (xb, yb, mb)

    def epoch(params, _):
        def step(params, b):
            if sample_mask is not None:
                g = grad_fn(params, b[0], b[1], activation, b[2])
            else:
                g = grad_fn(params, b[0], b[1], activation)
            return jax.tree.map(lambda p, gg: p - lr * gg, params, g), None

        params, _ = jax.lax.scan(step, params, batches)
        return params, None

    params, _ = jax.lax.scan(epoch, params, None, length=epochs)
    return params


class MnistClientModel(ClientModel):
    """The paper's Table-II MLP behind the engine's ``ClientModel`` surface.

    Data fields: ``x`` (n, 784) flattened images, ``y`` (n,) labels,
    ``activations`` () per-robot hidden activation id (0=ReLU, 1=Softmax).
    This family ships the fused Pallas ``local_sgd`` kernel and understands
    the size-bucketed packed layout.
    """

    family = "mnist_mlp"
    data_keys = ("x", "y", "activations")
    supports_fused = True
    packed_supported = True

    def __init__(self, cfg: MnistConfig | None = None):
        self.cfg = cfg if cfg is not None else MnistConfig()

    def init(self, key):
        return init_mnist(key, self.cfg)

    def loss(self, params, fields, sample_mask=None):
        return mnist_loss(
            params, fields["x"], fields["y"], fields["activations"],
            sample_mask,
        )

    def client_update(self, params, fields, *, lr, batch_size, epochs,
                      sample_mask=None):
        return local_sgd(
            params, fields["x"], fields["y"], lr=lr, batch_size=batch_size,
            epochs=epochs, activation=fields["activations"],
            sample_mask=sample_mask,
        )

    def metrics(self, params, eval_set):
        x, y = eval_set
        return mnist_loss(params, x, y), mnist_accuracy(params, x, y)

    def train_flops(self, sample_shape, *, epochs) -> float:
        # 2 * E * n * forward matmul flops — the paper's latency model
        return float(
            2 * epochs * sample_shape[0] * self.cfg.input_dim
            * self.cfg.hidden
        )

    # ------------------------------------------------- fused hot path
    def _split_flat(self, g_flat):
        """Slice the flat global vector back into the MLP's leaves, in the
        same sorted-key order ``core.engine.flatten`` concatenates them
        (b1, b2, w1, w2)."""
        cfg = self.cfg
        sizes = {
            "b1": (cfg.hidden,),
            "b2": (cfg.num_classes,),
            "w1": (cfg.input_dim, cfg.hidden),
            "w2": (cfg.hidden, cfg.num_classes),
        }
        out, off = {}, 0
        for k in ("b1", "b2", "w1", "w2"):
            n = 1
            for s in sizes[k]:
                n *= s
            out[k] = g_flat[off : off + n].reshape(sizes[k])
            off += n
        return out

    def fused_fits(self, width: int, batch_size: int) -> bool:
        """Whether a client's ``width`` samples fit one grid step of the
        fused kernel under its compiled VMEM limit."""
        cfg = self.cfg
        return fused_fits_vmem(width, cfg.input_dim, cfg.hidden,
                               cfg.num_classes, batch=batch_size)

    def fused_block_update(self, global_flat, fields, sample_mask, *,
                           lr, batch_size, epochs):
        """One ``pallas_call`` runs every client's whole masked
        epochs x batches loop (the engine routes here only when
        ``fused_fits`` admits the block width)."""
        x, y, act = fields["x"], fields["y"], fields["activations"]
        p = self._split_flat(global_flat)
        mm = (
            jnp.ones(x.shape[:2], bool) if sample_mask is None
            else sample_mask
        )
        new = local_sgd_fused(
            p["w1"], p["b1"], p["w2"], p["b2"], x, y, act, mm,
            lr=lr, batch_size=batch_size, epochs=epochs,
            interpret=interpret_mode(),
        )
        # flatten order must match ``flatten`` (dict leaves sort as
        # b1, b2, w1, w2)
        rows = x.shape[0]
        return jnp.concatenate(
            [new[k].reshape(rows, -1) for k in ("b1", "b2", "w1", "w2")],
            axis=1,
        )

    def fused_ragged_update(self, global_flat, blocks, *, lr, batch_size,
                            epochs):
        """The whole bucketed packed layout — ``blocks`` is a list of
        ``(fields, sample_mask)`` rectangles of differing widths — in ONE
        ragged-grid ``pallas_call`` (``local_sgd_fused_ragged``): every
        bucket's clients flatten into a single batch-tile buffer addressed
        by scalar-prefetched per-client offsets, so one launch replaces the
        per-bucket dispatch loop.  Returns the (sum rows, D) post-SGD flat
        params in block order (the engine routes here only when
        ``fused_fits`` admits one batch tile)."""
        xts, yts, mts, acts, nbs = [], [], [], [], []
        for fields, m in blocks:
            x, y = fields["x"], fields["y"]
            rows_b, w = x.shape[0], x.shape[1]
            nb = -(-w // batch_size)  # ceil: never drop real samples
            pad = nb * batch_size - w
            mm = jnp.ones(x.shape[:2], bool) if m is None else m
            if pad:
                x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
                y = jnp.pad(y, ((0, 0), (0, pad)))
                mm = jnp.pad(mm, ((0, 0), (0, pad)))
            xts.append(x.reshape(rows_b * nb, batch_size, -1))
            yts.append(y.reshape(rows_b * nb, batch_size))
            mts.append(mm.astype(jnp.float32).reshape(rows_b * nb,
                                                      batch_size))
            acts.append(fields["activations"])
            nbs.append(np.full(rows_b, nb, np.int32))
        nb_arr = np.concatenate(nbs)
        off = np.concatenate([[0], np.cumsum(nb_arr)[:-1]]).astype(np.int32)
        p = self._split_flat(global_flat)
        new = local_sgd_fused_ragged(
            p["w1"], p["b1"], p["w2"], p["b2"],
            jnp.concatenate(xts), jnp.concatenate(yts), jnp.concatenate(mts),
            jnp.concatenate(acts), jnp.asarray(nb_arr), jnp.asarray(off),
            lr=lr, epochs=epochs, nb_max=int(nb_arr.max()),
            interpret=interpret_mode(),
        )
        rows = nb_arr.shape[0]
        return jnp.concatenate(
            [new[k].reshape(rows, -1) for k in ("b1", "b2", "w1", "w2")],
            axis=1,
        )
