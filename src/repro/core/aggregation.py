"""Server-side aggregation strategies (§III.B.7, Algorithm 2 lines 13-14).

Operates on stacked flat client updates (N, D).  Every reduction is written
against the ``ClientComms`` collective vocabulary (``core/distributed.py``):
with the default identity comms this is the single-device simulation math;
inside the engine's ``shard_map`` the ``(N, D)`` operands are shard-local
client blocks, masks/weights stay replicated ``(N,)``, and the weighted
reduction becomes a psum across client shards.  The Pallas kernel
``kernels/fedavg_agg`` implements the same weighted reduction as a tiled TPU
kernel; ``fedavg_aggregate`` routes through it on accelerators
(``impl="auto"``) and falls back to an einsum on CPU.

With ``FedConfig.compress`` != "none" the engine decodes each client's
compressed uplink payload (``core/compress.py``) BEFORE this boundary:
every reduction here — the fused deviation psum, the weighted numerator,
``reduce_tree`` — consumes the decoded rows, so the O(N*D) client payload
is what compression shrinks while the (D,) cross-shard partials keep their
pinned reduction order and numerics.

Modes:
  fedavg    -- synchronous FedAvg [24]: wait for everyone (stragglers
               included); round time = max(latency).
  fedar     -- the paper: aggregate arrivals within timeout t, skip
               stragglers; round time = t.
  async     -- buffered no-wait (FedBuff-style): straggler updates land in a
               fixed-size per-client buffer and merge in a later round with a
               staleness-discounted weight; round time = t (server never
               blocks).  The buffer logic lives in ``core/engine.py``; the
               staleness-decayed weighted reduction is here / in the kernel.
  async_seq -- legacy FedAsync-style: fold updates one-by-one in arrival
               order with staleness-decayed mixing weight (O(N) sequential).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.common.config import FedConfig
from repro.core.distributed import ClientComms
from repro.kernels.fedavg_agg import fedavg_agg
from repro.kernels.ops import interpret_mode, resolve_impl

_IDENTITY = ClientComms()


def deviation_mask(
    deltas: jnp.ndarray,
    active: jnp.ndarray,
    gamma: float,
    *,
    comms: ClientComms = _IDENTITY,
    cohort=None,
):
    """Paper's ban trigger ``G^i - D_m^i > gamma``: robust z-score of each
    client's update distance from the active-population mean.

    ``deltas`` is shard-local (N_loc, D) under mesh comms; ``active`` is the
    replicated (N,) mask.  Returns the replicated (N,) deviated mask — the
    population mean/std come from ONE psum of shard partials (the (D,)
    weighted-delta sum with the scalar count fused into its tail slot: a
    psum is elementwise, so concatenating the operands is exact and saves a
    per-round collective dispatch) and a gather of the per-client
    distances.

    ``cohort=(canon, valid)``: selection-gated mode — ``deltas`` holds only
    this shard's gated cohort rows (every selected client, plus statically-
    padded slots with ``valid`` False); ``canon`` maps rows to local client
    slots.  Unselected clients' deltas are exact zeros and never active, so
    the statistics are over the same population — cohort mode just skips
    the O(N*D) sweeps for rows known to be zero (only summation order
    shifts, at fp32 ulp level)."""
    D = deltas.shape[1]
    if cohort is None:
        act_rows = comms.local(active)
    else:
        canon, valid = cohort
        act_rows = comms.local(active)[canon] & valid
    w = act_rows.astype(jnp.float32)[:, None]
    part = jnp.concatenate(
        [jnp.sum(deltas * w, axis=0), jnp.sum(w)[None]]
    )
    tot = comms.psum(part)  # (D + 1,): weighted delta sum + active count
    mean = tot[:D] / jnp.maximum(tot[D], 1.0)
    dist_rows = jnp.linalg.norm(deltas - mean, axis=1)
    if cohort is not None:
        # restore local client order (fill rows drop; non-cohort clients
        # read 0, which the active mask nan-filters out of the stats)
        canon, valid = cohort
        n_loc = comms.local(active).shape[0]
        dist_rows = jnp.zeros((n_loc,), dist_rows.dtype).at[
            jnp.where(valid, canon, n_loc)
        ].set(dist_rows, mode="drop")
    dist = comms.all_gather(dist_rows)  # (N,)
    act_dist = jnp.where(active, dist, jnp.nan)
    mu = jnp.nanmean(act_dist)
    sd = jnp.sqrt(jnp.nanmean((act_dist - mu) ** 2) + 1e-12)
    return active & (dist > mu + gamma * sd)


def fedavg_aggregate(
    global_flat,
    deltas,
    weights,
    mask,
    *,
    staleness=None,
    impl: str = "einsum",
    comms: ClientComms = _IDENTITY,
    cohort=None,
):
    """w <- w + sum_m mask_m * weight_m * s(tau_m) * delta_m / sum(...).

    ``staleness``: optional (N,) rounds-late per update, poly-decayed as
    ``(1 + tau)^-0.5`` (the buffered-async discount).  ``impl`` picks the
    reduction backend: "einsum" (XLA), "kernel" (Pallas ``fedavg_agg``,
    interpreted off-TPU), or "auto" (kernel on TPU, einsum elsewhere).

    Under mesh comms ``deltas`` is the shard-local (N_loc, D) block while
    ``weights`` / ``mask`` / ``staleness`` stay replicated (N,): the scalar
    denominator is computed on the full vectors (bit-identical to the
    single-device path) and only the (D,) numerator is a psum of per-shard
    partial reductions — the trust*staleness-weighted psum GSPMD schedules
    like a data-parallel gradient reduction.

    ``cohort=(canon, valid)``: selection-gated mode — ``deltas`` holds only
    the shard's gated cohort rows; ``canon``/``valid`` map them to local
    client slots.  Every contributing client is in the cohort and the rest
    are exact zeros, so the weighted numerator is the same sum with the
    zero rows skipped (fp32 ulp-level order shift); the denominator stays
    on the full replicated vectors either way."""
    w = weights * mask.astype(weights.dtype)
    decay = 1.0 if staleness is None else staleness_weight(staleness)
    denom = jnp.maximum(jnp.sum(w * decay), 1e-9)
    w_loc = comms.local(w)
    stale_loc = None if staleness is None else comms.local(staleness)
    if cohort is not None:
        canon, valid = cohort
        w_loc = w_loc[canon] * valid
        if stale_loc is not None:
            stale_loc = stale_loc[canon]
    if resolve_impl(impl, "agg") == "kernel":
        num = fedavg_agg(
            deltas, w_loc,
            staleness=stale_loc,
            interpret=interpret_mode(),
        )
    else:
        decay_loc = (
            1.0 if stale_loc is None else staleness_weight(stale_loc)
        )
        num = jnp.einsum("n,nd->d", w_loc * decay_loc, deltas)
    # cross-shard reduce of the (D,) per-shard partial: a flat psum by
    # default; the two-level tree (reduce-scatter + all-gather) when the
    # comms enable it (FedConfig.tree_reduce — the cohort engine's
    # hierarchical aggregation)
    return global_flat + comms.reduce_tree(num) / denom


def async_aggregate(
    global_flat, models, weights, mask, order, fed: FedConfig,
    *, comms: ClientComms = _IDENTITY,
):
    """Fold client MODELS (not deltas) in arrival order:
        w <- (1 - a_m) w + a_m w_m,  a_m = alpha * weight_m-normalized.
    ``order``: (N,) int32 permutation by arrival time; masked-out entries are
    skipped (mix weight 0).  The fold is inherently sequential over the
    global arrival order, so under mesh comms the shard-local models are
    all-gathered first — this legacy mode does not scale; use
    ``aggregation="async"`` for the buffered no-wait reduction."""
    models = comms.all_gather(models)
    wnorm = weights / jnp.maximum(jnp.max(weights), 1e-9)

    def body(g, idx):
        a = fed.staleness_alpha * wnorm[idx] * mask[idx].astype(jnp.float32)
        return (1.0 - a) * g + a * models[idx], None

    g, _ = jax.lax.scan(body, global_flat, order)
    return g


def staleness_weight(staleness, fed: FedConfig | None = None):
    """FedAsync poly decay: s(tau) = (1 + tau)^-0.5."""
    if fed is not None and fed.staleness_decay == "const":
        return jnp.ones_like(staleness)
    return (1.0 + staleness) ** -0.5
