"""Host-side client store: the fleet registry the cohort engine samples.

The resident engine keeps every client's trust, battery and defense history
as device state, which caps the fleet at what one scan carry fits.  The
store inverts that: ALL O(N * smallstate) bookkeeping lives in a sharded
numpy table on the host — trust score + the Algorithm 1 participation /
failure counters, the resource model (memory / bandwidth / battery /
compute), the (sketched) defense history rows, and activity bookkeeping
(``last_selected``) — and each round the engine

  1. samples a static-shape cohort K via ``selection.sample_cohort``
     (trust + CheckResource over the store's columns),
  2. ``gather``\\ s only those K clients' rows to device,
  3. runs the unchanged round body at cohort scope, and
  4. ``scatter_round``\\ s the updated trust / battery / history rows back
     and ``finish_round``\\ s the host-side evolution of everyone else
     (C_Interested for the eligible-but-not-sampled, the idle battery
     trickle — exactly the resident engine's update semantics, applied in
     numpy).

The table is split into ``num_shards`` contiguous blocks (``block``):
every column view is O(N / num_shards), so a multi-host serving layer can
own disjoint shards.  ``state_dict`` / ``load_state_dict`` round-trip the
whole table through ``checkpoint/ckpt.py`` (``save_store`` /
``restore_store``).
"""
from __future__ import annotations

import mmap
from typing import NamedTuple

import numpy as np

from repro.common.config import FedConfig
from repro.core.resources import BATTERY_COST, make_fleet
from repro.core.trust import TrustState


# Linux's MAP_NORESERVE; the mmap module only exports it from Python 3.13
_MAP_NORESERVE = getattr(mmap, "MAP_NORESERVE", 0x4000)


def _lazy_zeros(shape, dtype=np.float32) -> np.ndarray:
    """A zero-filled array whose pages are only backed once written.

    The per-client model-width columns (error-feedback residual, async
    pending delta) are (N, D): at a million clients of the 784-128-10 MLP
    that is ~400 GB of address space, of which a run only ever writes the
    rows of the clients it sampled.  ``np.zeros`` asks the kernel to
    account for all of it up front and is refused; an anonymous
    ``MAP_NORESERVE`` mapping is not accounted, reads of untouched rows
    hit the shared zero page, and memory grows with the rows written."""
    nbytes = int(np.prod(shape)) * np.dtype(dtype).itemsize
    if nbytes == 0:
        return np.zeros(shape, dtype)
    buf = mmap.mmap(
        -1, nbytes,
        flags=mmap.MAP_PRIVATE | mmap.MAP_ANONYMOUS | _MAP_NORESERVE,
    )
    return np.frombuffer(buf, dtype).reshape(shape)


class HostResources(NamedTuple):
    """Numpy view of the store's resource columns — duck-types
    ``ResourceState`` for the host-side selection math."""

    memory: np.ndarray
    bandwidth: np.ndarray
    battery: np.ndarray
    compute: np.ndarray


# the array-valued columns a checkpoint must round-trip, in one place so
# state_dict / load_state_dict / block can never drift apart
_COLUMNS = (
    "score", "participations", "failures",
    "memory", "bandwidth", "battery", "compute",
    "history", "residual", "last_selected",
    # store-resident async buffer (aggregation="async" in cohort mode):
    # the in-flight delta + its weight/issue/arrival tags follow the
    # client on and off the device (zero-width when async is off)
    "pending_delta", "pending_weight", "pending_issued",
    "pending_arrival", "pending_valid",
)


class ClientStore:
    """Numpy-backed per-client table: O(N * smallstate) host memory, plus
    O(D) for each client whose model-width rows were ever written."""

    def __init__(self, fed: FedConfig, history_dim: int, *,
                 residual_dim: int = 0, pending_dim: int = 0,
                 num_shards: int = 1):
        n = fed.num_clients
        if num_shards < 1 or n % num_shards:
            raise ValueError(
                f"num_clients={n} must divide into num_shards={num_shards} "
                f"contiguous store blocks"
            )
        self.fed = fed
        self.num_shards = num_shards
        res, self.poison_mask = make_fleet(
            n,
            num_starved=fed.num_starved,
            num_poisoners=fed.num_poisoners,
            seed=fed.seed,
        )
        self.score = np.full(n, fed.c_initial, np.float32)
        self.participations = np.zeros(n, np.int32)
        self.failures = np.zeros(n, np.int32)
        # np.array (copy): make_fleet returns device arrays whose np views
        # are read-only, and these columns mutate every round
        self.memory = np.array(res.memory)
        self.bandwidth = np.array(res.bandwidth)
        self.battery = np.array(res.battery)
        self.compute = np.array(res.compute)
        self.history = _lazy_zeros((n, history_dim))
        # error-feedback residuals (core/compress.py); width 0 when the
        # cohort engine runs uncompressed
        self.residual = _lazy_zeros((n, residual_dim))
        self.last_selected = np.full(n, -1, np.int32)
        # store-resident buffered-async slots (width 0 unless the cohort
        # engine runs aggregation="async"): the resident engine's
        # EngineState.pending_* leaves, host-side
        self.pending_delta = _lazy_zeros((n, pending_dim))
        self.pending_weight = np.zeros(n, np.float32)
        self.pending_issued = np.zeros(n, np.int32)
        self.pending_arrival = np.zeros(n, np.int32)
        self.pending_valid = np.zeros(n, bool)
        # 0-d array (not a python int) so the ckpt pytree flattens it
        self.round_idx = np.zeros((), np.int32)

    # ------------------------------------------------------------------
    @property
    def num_clients(self) -> int:
        return self.score.shape[0]

    @property
    def history_dim(self) -> int:
        return self.history.shape[1]

    @property
    def residual_dim(self) -> int:
        return self.residual.shape[1]

    @property
    def pending_dim(self) -> int:
        return self.pending_delta.shape[1]

    def block(self, shard: int) -> dict:
        """Shard ``shard``'s contiguous column views (zero-copy): clients
        ``[shard * N/k, (shard + 1) * N/k)`` — the O(N/k) slice a
        multi-host registry would own."""
        if not 0 <= shard < self.num_shards:
            raise IndexError(
                f"shard {shard} out of range for {self.num_shards} blocks"
            )
        blk = self.num_clients // self.num_shards
        sl = slice(shard * blk, (shard + 1) * blk)
        return {name: getattr(self, name)[sl] for name in _COLUMNS}

    def trust_view(self) -> TrustState:
        return TrustState(self.score, self.participations, self.failures)

    def resources_view(self) -> HostResources:
        return HostResources(
            self.memory, self.bandwidth, self.battery, self.compute
        )

    # ------------------------------------------------------------------
    def gather(self, idx) -> dict:
        """Copy the cohort's rows out of the table: the O(K * smallstate)
        payload that moves to device each round."""
        idx = np.asarray(idx)
        return {
            "score": self.score[idx],
            "participations": self.participations[idx],
            "failures": self.failures[idx],
            "memory": self.memory[idx],
            "bandwidth": self.bandwidth[idx],
            "battery": self.battery[idx],
            "compute": self.compute[idx],
            "history": self.history[idx],
            "residual": self.residual[idx],
            "pending_delta": self.pending_delta[idx],
            "pending_weight": self.pending_weight[idx],
            "pending_issued": self.pending_issued[idx],
            "pending_arrival": self.pending_arrival[idx],
            "pending_valid": self.pending_valid[idx],
        }

    def scatter_round(self, idx, valid, *, trust: TrustState, battery,
                      history, residual=None, pending=None) -> None:
        """Write the round's device results back into the table — only the
        ``valid`` cohort slots land (underfill slots carry garbage rows
        gathered from client 0 and must never scatter).  ``pending`` is the
        optional dict of post-round async buffer columns (keys named like
        the store columns)."""
        idx = np.asarray(idx)[np.asarray(valid, bool)]
        keep = np.asarray(valid, bool)
        self.score[idx] = np.asarray(trust.score)[keep]
        self.participations[idx] = np.asarray(trust.participations)[keep]
        self.failures[idx] = np.asarray(trust.failures)[keep]
        self.battery[idx] = np.asarray(battery)[keep]
        if self.history_dim:
            self.history[idx] = np.asarray(history)[keep]
        if self.residual_dim and residual is not None:
            self.residual[idx] = np.asarray(residual)[keep]
        if self.pending_dim and pending is not None:
            for name in ("pending_delta", "pending_weight",
                         "pending_issued", "pending_arrival",
                         "pending_valid"):
                getattr(self, name)[idx] = np.asarray(pending[name])[keep]

    def finish_round(self, idx, valid, eligible) -> None:
        """Host-side evolution of the NON-cohort population, mirroring the
        resident round body: eligible-but-not-sampled clients earn
        ``c_interested`` (Algorithm 1's interest credit), every non-
        participant trickle-charges battery at ``BATTERY_COST / 4``, and
        the cohort's activity stamp + the round counter advance."""
        in_cohort = np.zeros(self.num_clients, bool)
        live = np.asarray(idx)[np.asarray(valid, bool)]
        in_cohort[live] = True
        interested = np.asarray(eligible, bool) & ~in_cohort
        self.score[interested] += np.float32(self.fed.c_interested)
        idle = ~in_cohort
        self.battery[idle] = np.minimum(
            self.battery[idle] + BATTERY_COST / 4, 1.0
        )
        self.last_selected[live] = int(self.round_idx)
        self.round_idx = self.round_idx + np.int32(1)

    # ------------------------------------------------------------------
    def state_dict(self) -> dict:
        """Checkpoint pytree: every mutable column + the round counter."""
        out = {name: getattr(self, name) for name in _COLUMNS}
        out["round_idx"] = self.round_idx
        return out

    def load_state_dict(self, state: dict) -> None:
        for name in _COLUMNS:
            if name not in state:
                raise ValueError(
                    f"store checkpoint is missing column {name!r} — it was "
                    f"written by an older build without that column; "
                    f"re-save the store (or restore with the build that "
                    f"wrote it)"
                )
            arr = np.asarray(state[name])
            if arr.shape != getattr(self, name).shape:
                raise ValueError(
                    f"store column {name!r}: checkpoint shape {arr.shape} "
                    f"vs store {getattr(self, name).shape}"
                )
            setattr(self, name, arr.astype(getattr(self, name).dtype))
        self.round_idx = np.asarray(state["round_idx"], np.int32).reshape(())
