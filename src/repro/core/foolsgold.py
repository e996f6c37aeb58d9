"""FoolsGold sybil/poisoning mitigation [26] (§III.B.6) — similarity math.

Clients that repeatedly send *similar* gradient updates (sybils pushing a
common poisoned objective) get their aggregation learning rate scaled down.
Two weightings share the machinery here (strategy selection and history
sketching live in ``core/defense.py``):

``foolsgold_weights``
    Fung et al.'s original statistic: max pairwise cosine over historical
    aggregate updates, pardoning, then logit re-scaling.  Correct for the
    paper's 12 heterogeneous robots, but it *misfires on homogeneous
    fleets* — honest clients that share a data profile reach pairwise
    cosine 0.99+, indistinguishable *by value* from sybil replicas at 1.0
    (and a JL sketch blurs the gap further).

``cluster_weights``
    The cluster-aware variant: what separates a sybil clique from a
    natural cluster of honest look-alikes is its *mass*, not its
    similarity level.  Each client's effective cluster multiplicity
    ``m_i = 1 + sum_j relu(cs_ij)^power`` soft-counts its near-duplicates;
    clients keep full weight while ``m_i`` stays within ``slack *
    median_active(m)`` (the fleet's natural cluster scale), and larger
    cliques decay as ``(slack * median / m)^sharpness`` — so a replica
    clique's combined influence collapses toward one client's, while an
    honest homogeneous fleet keeps uniform weights (aggregation matches
    the defense-off run).

The pairwise (N, N) cosine matrix is the engine's one all-to-all.  Written
against ``ClientComms`` it becomes a gathered block product: each client
shard row-normalizes its local history block, the unit rows travel through
the ``gather_defense`` collective, and every shard computes only its
(N_loc, N) similarity block — through the Pallas ``sketch_similarity``
kernel on TPU (``impl="auto"``/"kernel") or an einsum elsewhere.  With
identity comms this reduces exactly to the dense single-device math.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.distributed import ClientComms
from repro.kernels.defense_sim import sketch_similarity
from repro.kernels.ops import interpret_mode, resolve_impl

_IDENTITY = ClientComms()


def _row_offset(comms: ClientComms, n_loc: int):
    """Global client index of this shard's first row (0 on one device)."""
    if comms.axis is None:
        return 0
    return jax.lax.axis_index(comms.axis) * n_loc


def _similarity_block(history, active, *, comms: ClientComms, impl: str):
    """Row-normalize the shard-local history block, gather the unit rows,
    and return the masked (N_loc, N) cosine block (self-similarity zeroed,
    inactive pairs at -1) plus the shard's local active mask."""
    N = active.shape[0]
    n_loc = history.shape[0]
    norm = jnp.linalg.norm(history, axis=1, keepdims=True)
    unit = history / jnp.maximum(norm, 1e-9)
    unit_full = comms.gather_defense(unit)  # (N, d) — the one all-to-all
    if resolve_impl(impl, "defense") == "kernel":
        cs = sketch_similarity(
            unit, unit_full, interpret=interpret_mode()
        )
    else:
        cs = unit @ unit_full.T  # (N_loc, N) local similarity block
    # zero the self-similarity diagonal of this shard's block
    rows = jnp.arange(n_loc) + _row_offset(comms, n_loc)
    cs = cs - (rows[:, None] == jnp.arange(N)[None, :]).astype(cs.dtype)
    active_loc = comms.local(active)
    cs = jnp.where(active_loc[:, None] & active[None, :], cs, -1.0)
    return cs, active_loc


def foolsgold_weights(
    history: jnp.ndarray,
    active: jnp.ndarray,
    *,
    comms: ClientComms = _IDENTITY,
    impl: str = "einsum",
) -> jnp.ndarray:
    """history: shard-local (N_loc, D) per-client cumulative update vectors.
    active: replicated (N,) bool — clients contributing this round.
    Returns replicated (N,) aggregation weights in [0, 1]."""
    cs, active_loc = _similarity_block(history, active, comms=comms, impl=impl)

    maxcs_loc = jnp.max(cs, axis=1)  # v_i for this shard's rows
    maxcs = comms.all_gather(maxcs_loc)  # (N,) v_j for every column
    # pardoning: if v_j > v_i, rescale cs_ij by v_i / v_j
    ratio = maxcs_loc[:, None] / jnp.maximum(maxcs[None, :], 1e-9)
    cs = jnp.where(maxcs[None, :] > maxcs_loc[:, None], cs * ratio, cs)

    wv = 1.0 - jnp.max(cs, axis=1)
    # numerically safe clamp: wv -> [0, 0.99] keeps the logit finite without
    # the old exact ``wv == 1.0`` float compare (which missed 1 - eps)
    wv = jnp.clip(wv, 0.0, 0.99)
    # logit re-scaling (kappa = 0.5 midpoint as in the paper's release)
    logit = jnp.log(wv / jnp.maximum(1.0 - wv, 1e-9) + 1e-9) + 0.5
    wv = jnp.clip(logit, 0.0, 1.0)
    return comms.all_gather(jnp.where(active_loc, wv, 0.0))


def cluster_weights(
    history: jnp.ndarray,
    active: jnp.ndarray,
    *,
    comms: ClientComms = _IDENTITY,
    impl: str = "einsum",
    power: float = 8.0,
    slack: float = 5.0,
    sharpness: float = 3.0,
) -> jnp.ndarray:
    """Cluster-aware weighting over a (sketched) history block.

    ``m_i = 1 + sum_j relu(cs_ij)^power`` is client i's effective cluster
    multiplicity (1 = no near-duplicates; a k-replica sybil of i pushes it
    toward k).  The fleet's natural cluster scale is the *median* active
    multiplicity — robust to a sybil minority inflating the tail — and
    weights only drop once a cluster outgrows ``slack`` times it:

        w_i = clip(slack * median / m_i, 0, 1) ** sharpness

    An honest homogeneous fleet (every profile cluster near the median
    scale) keeps w = 1 everywhere, so aggregation matches the defense-off
    run; a replica clique of k >> slack * median collapses to combined
    influence ~ slack * median clients."""
    cs, active_loc = _similarity_block(history, active, comms=comms, impl=impl)
    m_loc = 1.0 + jnp.sum(jnp.clip(cs, 0.0, 1.0) ** power, axis=1)
    m = comms.all_gather(m_loc)  # (N,) replicated multiplicities
    med = jnp.nanmedian(jnp.where(active, m, jnp.nan))
    med = jnp.nan_to_num(med, nan=1.0)  # empty round -> neutral scale
    wv = jnp.clip(slack * med / jnp.maximum(m_loc, 1.0), 0.0, 1.0) ** sharpness
    return comms.all_gather(jnp.where(active_loc, wv, 0.0))


def update_history(
    history: jnp.ndarray,
    deltas: jnp.ndarray,
    active: jnp.ndarray,
    *,
    decay: float = 1.0,
    comms: ClientComms = _IDENTITY,
):
    """Accumulate flattened client deltas into the similarity history.
    ``history`` / ``deltas`` are shard-local blocks; ``active`` replicated.
    ``decay`` < 1 exponentially forgets old rounds so unbounded runs don't
    saturate fp32 (1.0 reproduces the legacy accumulate-forever behavior)."""
    return decay * history + jnp.where(
        comms.local(active)[:, None], deltas, 0.0
    )
