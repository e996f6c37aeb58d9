"""Uplink delta-compression subsystem (selected via ``FedConfig.compress``).

Resource-constrained FL surveys rank uplink payload as the binding
constraint for mobile-robot fleets, yet the engine's clients ship raw fp32
``(D,)`` deltas.  This registry mirrors ``core/defense.py``: a strategy
owns the per-client error-feedback residual block carried in the engine
scan state (and the ``ClientStore`` ``residual`` column in cohort mode)
and the encode/decode pair applied at the client->aggregator boundary:

  ``none`` -- raw deltas, zero-width residual; the engine skips the
              roundtrip entirely, bit-identical to the uncompressed path.
  ``qsgd`` -- stochastic uniform quantization (Alistarh et al.) at
              ``compress_bits`` in {4, 8}: per-client max-|v| scale, codes
              stochastically rounded so the decode is UNBIASED over keys,
              packed to uint8 (two nibbles per byte at 4 bits) via
              ``kernels/compress.py``.  Payload ~ D*bits/8 + 4 bytes per
              client (vs 4*D dense).
  ``topk`` -- magnitude top-``compress_k`` sparsification: the k largest-
              |v| coordinates ship as (value, index) pairs — 8*k bytes per
              client.  Biased, so error feedback is what makes it sound.
              The round selects each row's exact k-th magnitude
              (``kernels/topk_select.py``) and decodes by a kept mask
              over the encode's input (``kept_topk``), never sorting or
              scattering; ``decode(payload)`` is the decoder of a
              received payload.

Error feedback (EF-SGD): each client compresses ``delta + residual`` and
carries ``residual' = (delta + residual) - decode(payload)`` to the next
round it transmits.  Unselected clients keep their residual untouched and
contribute exact zeros.  The sum of decoded payloads plus the final
residual telescopes to the sum of raw deltas (pinned to fp32 tolerance by
``tests/test_compress.py``), so compression error never accumulates.

Determinism across shardings: the stochastic-rounding bits are drawn from
per-client keys folded from the CANONICAL client id (not the shard-local
row), so a 1-device run and an 8-shard run quantize bit-identically.

Payload model (what actually crosses which wire): the encode/decode pair
compresses the per-client uplink — the (N, D) block that selection-gated
gathers, the deviation screen and the defense history would otherwise
consume at fp32.  The cross-shard reduction (``MeshComms.reduce_tree`` /
the aggregation psum) runs over the already-reduced (D,) partial per
device, which is O(D) independent of N either way; decoded-then-reduced
keeps those collectives' pinned numerics while the O(N*D) client payload
drops by the mode's nominal ratio.
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.common.config import FedConfig
from repro.common.tracing import phase
from repro.kernels import ops, ref
from repro.kernels.topk_select import topk_select_fits_vmem

__all__ = ["CompressionStrategy", "NoCompression", "QSGDCompression",
           "TopKCompression", "kept_topk", "make_compression"]


class CompressionStrategy:
    """Interface the engine round body calls, strategy-agnostically.

    ``active``          -- False only for ``none``; lets the engine skip
                           the roundtrip (and carry a width-0 residual) so
                           the uncompressed path stays bit-identical.
    ``residual_dim``    -- width of the carried per-client error-feedback
                           block (0 = stateless).
    ``payload_nbytes``  -- nominal uplink bytes per client per round (the
                           bench/perf-gate payload model).
    ``encode``          -- compress ``deltas + residual`` (per-row keys for
                           stochastic codes); returns the payload pytree and
                           the post-encode residual for every row.  The
                           engine masks both on the transmit mask.
    ``decode``          -- payload pytree -> (n, D) fp32 decoded deltas.
    ``route``           -- the backend the codec's hot op takes at width D:
                           ``"kernel"`` (Pallas), ``"einsum"`` (XLA) or
                           ``"none"`` (``FedAREngine.kernel_routes``).
    """

    name = "none"
    active = False

    def residual_dim(self, model_dim: int) -> int:
        return 0

    def payload_nbytes(self, model_dim: int) -> int:
        return 4 * model_dim  # dense fp32

    def route(self, model_dim: int) -> str:
        return "none"

    def encode(self, deltas, residual, keys) -> Tuple[dict, jnp.ndarray]:
        raise NotImplementedError

    def decode(self, payload, model_dim: int):
        raise NotImplementedError

    def roundtrip(self, deltas, residual, transmit, keys):
        """The engine's one call: encode/decode ``deltas + residual`` and
        apply error feedback, gated on the shard-local ``transmit`` mask.
        Returns ``(decoded, new_residual, payload)`` where non-transmitting
        rows decode to exact zeros and keep their residual untouched.  The
        two halves run under the ``codec.encode`` / ``codec.decode`` phase
        scopes (``common/tracing.py``)."""
        m = transmit[:, None]
        with phase("codec.encode"):
            payload, res = self.encode(deltas, residual, keys)
            res = jnp.where(m, res, residual)
        with phase("codec.decode"):
            dec = jnp.where(m, self.decode(payload, deltas.shape[-1]), 0.0)
        return dec, res, payload


class NoCompression(CompressionStrategy):
    """Raw fp32 deltas; the engine never calls encode/decode."""

    def encode(self, deltas, residual, keys):
        return {"dense": deltas + residual}, jnp.zeros_like(residual)

    def decode(self, payload, model_dim: int):
        return payload["dense"]


class QSGDCompression(CompressionStrategy):
    """Stochastic uniform quantization at ``compress_bits`` levels.

    ``L = 2^(bits-1) - 1`` levels per sign; code ``q = round_stoch(|v| /
    scale * L) * sign(v)`` with per-row ``scale = max|v|``, shipped
    offset-encoded (``q + L``) in packed uint8.  Stochastic rounding makes
    the decode ``q * scale / L`` unbiased in expectation over keys; an
    all-zero row (scale 0) encodes and decodes to exact zeros."""

    name = "qsgd"
    active = True

    def __init__(self, fed: FedConfig, model_dim: int):
        if fed.compress_bits not in (4, 8):
            raise ValueError(
                f"FedConfig.compress_bits={fed.compress_bits!r} unsupported "
                "for compress='qsgd' — the uint8 pack kernel handles 4 "
                "(two codes per byte) or 8 (one code per byte)"
            )
        self.bits = fed.compress_bits
        self.levels = 2 ** (fed.compress_bits - 1) - 1
        self.impl = fed.compress_impl

    def residual_dim(self, model_dim: int) -> int:
        return model_dim

    def payload_nbytes(self, model_dim: int) -> int:
        return math.ceil(model_dim * self.bits / 8) + 4  # codes + fp32 scale

    def route(self, model_dim: int) -> str:
        return ops.resolve_impl(self.impl, "compress")

    def encode(self, deltas, residual, keys):
        v = (deltas + residual).astype(jnp.float32)
        L = float(self.levels)
        scale = jnp.max(jnp.abs(v), axis=-1, keepdims=True)  # (n, 1)
        safe = jnp.where(scale > 0.0, scale, 1.0)
        u = jnp.abs(v) / safe * L  # in [0, L]
        low = jnp.floor(u)
        unif = jax.vmap(lambda k: jax.random.uniform(k, v.shape[-1:]))(keys)
        q = (low + (unif < u - low)).astype(jnp.int32)  # stochastic round
        q = jnp.where(scale > 0.0, q * jnp.sign(v).astype(jnp.int32), 0)
        codes = (q + self.levels).astype(jnp.int32)  # offset to [0, 2L]
        packed = ops.pack_codes(
            codes, bits=self.bits,
            use_pallas=self.route(v.shape[-1]) == "kernel")
        payload = {"codes": packed, "scale": scale.astype(jnp.float32)}
        return payload, v - self.decode(payload, v.shape[-1])

    def decode(self, payload, model_dim: int):
        codes = ops.unpack_codes(payload["codes"], bits=self.bits,
                                 dim=model_dim,
                                 use_pallas=self.route(model_dim) == "kernel")
        q = codes.astype(jnp.float32) - float(self.levels)
        return q * payload["scale"] / float(self.levels)


class TopKCompression(CompressionStrategy):
    """Magnitude top-``compress_k``: ship the k largest-|v| coordinates as
    (value, index) pairs.  ``k == D`` is an exact identity; ``k`` defaults
    to ``D // 32`` when ``FedConfig.compress_k`` is unset.  Biased — the
    engine's error feedback carries what was dropped into the next round.
    The kept set comes from each row's exact k-th key and last kept tie
    (``ops.topk_select``: the ``kernels/topk_select.py`` kernel where
    ``compress_impl`` resolves to it and a block of the row width fits its
    VMEM, else the ``jax.numpy`` bisection), and the round decodes by the
    kept mask (``kept_topk``), sorting and scattering nothing."""

    name = "topk"
    active = True

    def __init__(self, fed: FedConfig, model_dim: int):
        k = fed.compress_k if fed.compress_k is not None else max(
            1, model_dim // 32
        )
        if not 1 <= k <= model_dim:
            raise ValueError(
                f"FedConfig.compress_k={fed.compress_k!r} out of range for "
                f"compress='topk' with model_dim={model_dim} — need "
                f"1 <= k <= D (k == D is the exact-identity degenerate case)"
            )
        if fed.compress_impl == "kernel" and not topk_select_fits_vmem(
                model_dim):
            raise ValueError(
                f'compress_impl="kernel": a block of width {model_dim} does '
                f"not fit the top-k selection kernel's compiled VMEM limit; "
                f'use compress_impl="auto" or "einsum" for this model'
            )
        self.k = int(k)
        self.impl = fed.compress_impl

    def residual_dim(self, model_dim: int) -> int:
        return model_dim

    def payload_nbytes(self, model_dim: int) -> int:
        return 8 * self.k  # fp32 value + int32 index per kept coordinate

    def route(self, model_dim: int) -> str:
        """The selection's backend: ``"kernel"`` where ``compress_impl``
        resolves to it and a block of width ``model_dim`` fits the
        kernel's VMEM (an explicit ``"kernel"`` that does not fit was
        refused at construction), else ``"einsum"``."""
        impl = ops.resolve_impl(self.impl, "compress")
        if impl == "kernel" and not topk_select_fits_vmem(model_dim):
            return "einsum"
        return impl

    def _payload(self, v):
        """``lax.top_k``'s (value, index) pairs by magnitude, ties to the
        lower index: what crosses the wire."""
        _, idx = jax.lax.top_k(jnp.abs(v), self.k)
        return {"vals": jnp.take_along_axis(v, idx, axis=-1),
                "idx": idx.astype(jnp.int32)}

    def _kept(self, v):
        """``v`` with every coordinate outside its top-k set zeroed."""
        thr, last = ops.topk_select(
            v, k=self.k, use_pallas=self.route(v.shape[-1]) == "kernel")
        return kept_topk(v, thr, last)

    def encode(self, deltas, residual, keys):
        v = (deltas + residual).astype(jnp.float32)
        return self._payload(v), v - self._kept(v)

    def decode(self, payload, model_dim: int):
        """The decoder of a received payload: scatter-add the pairs into
        zero rows.  The round itself never scatters (``roundtrip``)."""
        return ref.topk_decode_ref(payload["vals"], payload["idx"],
                                   model_dim)

    def roundtrip(self, deltas, residual, transmit, keys):
        """``CompressionStrategy.roundtrip`` with no sort and no scatter:
        client and server run in one program, so the decoded rows are the
        encode's own kept rows, bit-identical to decoding the payload.
        The payload is returned as its ``jax.eval_shape`` structure, for
        the wire's accounting.  The scopes split as for every codec: the
        selection, the kept mask and the error feedback under
        ``codec.encode``, the masked decoded rows under ``codec.decode``."""
        m = transmit[:, None]
        with phase("codec.encode"):
            v = (deltas + residual).astype(jnp.float32)
            dec = self._kept(v)
            res = jnp.where(m, v - dec, residual)
        with phase("codec.decode"):
            dec = jnp.where(m, dec, 0.0)
        return dec, res, jax.eval_shape(self._payload, v)


def kept_topk(v, thr, last):
    """``v`` with every coordinate outside its top-k set replaced by +0.0,
    given each row's k-th largest key ``thr`` and the column ``last`` of
    its last kept tie, both ``(n, 1)`` (``ops.topk_select``).  The int32
    view of a non-negative float orders exactly as the float total order
    does (NaN above +inf), and ``top_k`` breaks ties toward the lower
    index, so the kept set is the keys above ``thr`` plus the ties at
    ``thr`` up to ``last``."""
    key = jax.lax.bitcast_convert_type(jnp.abs(v), jnp.int32)
    cols = jax.lax.broadcasted_iota(jnp.int32, v.shape, v.ndim - 1)
    kept = (key > thr) | ((key == thr) & (cols <= last))
    # a kept zero decodes to +0.0 as 0.0 + (-0.0) does; XLA would fold a
    # trailing ``+ 0.0`` away, so the zero test does it
    return jnp.where(kept & (v != 0.0), v, 0.0)


_STRATEGIES = {
    "none": NoCompression,
    "qsgd": QSGDCompression,
    "topk": TopKCompression,
}


def make_compression(fed: FedConfig, model_dim: int) -> CompressionStrategy:
    """Build the strategy ``FedConfig.compress`` names (validating the
    bits/k knobs and the aggregation-mode combo)."""
    try:
        cls = _STRATEGIES[fed.compress]
    except KeyError:
        raise ValueError(
            f"unknown FedConfig.compress={fed.compress!r} "
            f"(known: {sorted(_STRATEGIES)})"
        ) from None
    if cls is NoCompression:
        return NoCompression()
    if fed.aggregation == "async_seq":
        raise ValueError(
            f"FedConfig.compress={fed.compress!r} does not compose with "
            "aggregation='async_seq': the sequential fold aggregates full "
            "local MODELS, never the decoded deltas, so the error-feedback "
            "residual would silently drift from what lands in the global "
            "model — use aggregation='async' (the buffered mode transmits "
            "exactly when its slot can admit) or compress='none'"
        )
    return cls(fed, model_dim)


def client_keys(key, client_ids):
    """Per-client stochastic-code keys folded from CANONICAL client ids, so
    quantization bits are identical across 1-device and sharded runs."""
    return jax.vmap(lambda c: jax.random.fold_in(key, c))(client_ids)


def make_residual(num_clients: int, residual_dim: int,
                  dtype=jnp.float32) -> Optional[jnp.ndarray]:
    """Fresh all-zero residual block (width 0 when compression is off)."""
    return jnp.zeros((num_clients, residual_dim), dtype)
