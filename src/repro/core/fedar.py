"""FedAR end-to-end simulation — Algorithm 2, paper-faithful.

Simulates the robot fleet of §IV: heterogeneous resources, stragglers
(latency > timeout), poisoners (label-flipped local data), trust evolution,
and the aggregation modes.  All round math lives in
:mod:`repro.core.engine` — ``FedARServer`` is a thin host-side wrapper that
keeps the seed's public API (``run_round`` / ``run`` + a ``history`` dict of
per-round rows) while delegating to the fully-jitted engine.  ``run`` executes
every round inside one ``lax.scan`` by default (``driver="scan"``);
``driver="python"`` keeps the one-jitted-dispatch-per-round loop.

Multi-device: pass ``FedConfig(mesh_shape=k)`` to run the engine's rounds
sharded over a ``clients`` mesh axis (``core/distributed.py``) — the server
API and history layout are unchanged; a host with fewer devices than
``mesh_shape`` is an error.  ``FedARServer.mesh`` exposes the active mesh
(``None`` when unsharded).
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from repro.common import tracing
from repro.common.config import FedConfig
from repro.common.tracing import span
from repro.core.engine import (
    CohortEngine,
    FedAREngine,
    RoundOutputs,
    flatten,
    unflatten,
)
from repro.core.resources import TaskRequirement

__all__ = ["FedARServer", "flatten", "unflatten"]

# the per-round outputs the history keeps
_HISTORY_FIELDS = ("trust", "selected", "on_time", "round_time", "loss",
                   "acc")


@dataclass
class FedARServer:
    """Holds server-side state and runs communication rounds.

    ``cfg`` is either an ``MnistConfig`` (coerced to the paper's MLP client
    by the engine, the seed API) or any :class:`repro.models.client
    .ClientModel` — e.g. ``LMClientModel`` for transformer fleets."""

    cfg: Any
    fed: FedConfig
    req: TaskRequirement
    lr: float = 0.1

    def __post_init__(self):
        # cohort_size >= N: the "cohort" is the whole fleet — strip the knob
        # and run the resident engine, bit-identical to the pre-cohort path
        if (
            self.fed.cohort_size is not None
            and self.fed.cohort_size >= self.fed.num_clients
        ):
            self.fed = dataclasses.replace(self.fed, cohort_size=None)
        self.cohort_mode = self.fed.cohort_size is not None
        if self.cohort_mode:
            self.engine = CohortEngine(self.cfg, self.fed, self.req,
                                       lr=self.lr)
            self.state = None  # server state lives in engine.store/params
        else:
            self.engine = FedAREngine(self.cfg, self.fed, self.req,
                                      lr=self.lr)
            self.state = self.engine.init_state()
        self.template = self.engine.template
        self.dim = self.engine.dim
        self.poison_mask = self.engine.poison_mask
        self.history: Dict[str, List[Any]] = {
            "trust": [],
            "selected": [],
            "on_time": [],
            "loss": [],
            "acc": [],
            "round_time": [],
        }
        if self.cohort_mode:
            # per-round (K,) client indices + slot-validity of the sampled
            # cohort; the trust/selected/on_time rows above are cohort-
            # indexed in this mode (row j -> fleet client cohort[r][0][j])
            self.history["cohort"] = []

    # -- live views of the engine carry (the seed exposed these directly) --
    @property
    def mesh(self):
        """The engine's ``clients`` mesh, or ``None`` on a single device."""
        return self.engine.mesh

    @property
    def params(self):
        flat = self.engine.params if self.cohort_mode else self.state.params
        return unflatten(flat, self.template)

    @property
    def trust(self):
        if self.cohort_mode:
            return self.engine.store.trust_view()
        return self.state.trust

    @property
    def resources(self):
        if self.cohort_mode:
            return self.engine.store.resources_view()
        return self.state.resources

    @property
    def fg_history(self):
        if self.cohort_mode:
            return self.engine.store.history
        return self.state.fg_history

    @property
    def round_idx(self) -> int:
        if self.cohort_mode:
            return self.engine.round_idx
        return int(self.state.round_idx)

    # ------------------------------------------------------------------
    def _fetch(self, out: RoundOutputs, rounds: int) -> dict:
        """The history's outputs copied to the host, each distinct array
        once, under the ``fedar.fetch`` span (stats ``copies`` and
        ``bytes``).  With a trace running and the codec on, the codec's
        count rides along: stats ``codec_rows_sent`` and
        ``codec_rows_encoded``, the rows the codec transmitted and the rows
        it encoded."""
        names = list(_HISTORY_FIELDS)
        traced = tracing.enabled()
        codec = traced and self.engine.codec_rows > 0
        if codec:
            names.append("codec_rows_sent")
        arrays = {n: getattr(out, n) for n in names}
        distinct = {id(a): a for a in arrays.values()}
        with span("fedar.fetch") as s:
            copied = {k: np.asarray(a) for k, a in distinct.items()}
            host = {n: copied[id(a)] for n, a in arrays.items()}
            if traced:
                stats = {"copies": len(distinct),
                         "bytes": sum(a.nbytes for a in distinct.values())}
                if codec:
                    stats["codec_rows_sent"] = int(
                        host.pop("codec_rows_sent").sum())
                    stats["codec_rows_encoded"] = (self.engine.codec_rows
                                                   * rounds)
                s.set_metadata(**stats)
        return host

    def _append(self, host: dict, rounds: int, with_eval: bool):
        """Host bookkeeping: fold stacked (or single-round) host outputs
        into the seed-format history dict."""
        trust = np.atleast_2d(host["trust"])
        selected = np.atleast_2d(host["selected"])
        on_time = np.atleast_2d(host["on_time"])
        round_time = np.reshape(host["round_time"], (rounds,))
        loss = np.reshape(host["loss"], (rounds,))
        acc = np.reshape(host["acc"], (rounds,))
        for r in range(rounds):
            self.history["trust"].append(trust[r])
            self.history["selected"].append(selected[r])
            self.history["on_time"].append(on_time[r])
            self.history["round_time"].append(float(round_time[r]))
            if with_eval:
                self.history["loss"].append(float(loss[r]))
                self.history["acc"].append(float(acc[r]))

    def _resident_data(self, data):
        """Resident engines consume the prepared array dict; a fleet object
        (``FederatedDataset`` / ``VirtualFleet``) passed instead is
        materialized + prepared here, so call sites can hand the same fleet
        to a cohort server and a resident one."""
        if hasattr(data, "cohort_arrays"):
            ds = data.materialize() if hasattr(data, "materialize") else data
            return self.engine.prepare_data(ds)
        return data

    # ------------------------------------------------------------------
    def run_round(self, data, *, eval_set=None, force_straggler=None):
        """One communication round (one jitted dispatch + host sync).
        ``data``: dict with stacked per-client arrays x (N, n, 784), y (N, n),
        sizes (N,), activations (N,) int32 (0=relu, 1=softmax, Table II) —
        or, in cohort mode, a fleet object exposing ``cohort_arrays``.

        The round is the host span ``fedar.round``; its children are the
        engine's spans, ``fedar.wait`` (the device finishing the round),
        ``fedar.fetch`` and ``fedar.history``."""
        with span("fedar.round"):
            if self.cohort_mode:
                if force_straggler is not None:
                    raise ValueError(
                        "force_straggler is a resident-engine test hook; "
                        "the cohort engine has no stable client axis to "
                        "force"
                    )
                idx, valid, out = self.engine.run_round(data,
                                                        eval_set=eval_set)
            else:
                with span("fedar.prepare"):
                    data = self._resident_data(data)
                    force = (None if force_straggler is None
                             else jnp.asarray(force_straggler))
                self.state, out = self.engine.step(
                    self.state, data, eval_set=eval_set,
                    force_straggler=force,
                )
                with span("fedar.wait"):
                    jax.block_until_ready(out)
            host = self._fetch(out, 1)
            with span("fedar.history"):
                self._append(host, 1, eval_set is not None)
                if self.cohort_mode:
                    self.history["cohort"].append(
                        (np.asarray(idx), np.asarray(valid))
                    )
            return host["selected"], host["on_time"]

    def run(self, data, rounds: int, eval_set=None, force_straggler=None,
            driver: str = "scan"):
        """Run ``rounds`` communication rounds.

        driver="scan"   -- all rounds inside one ``lax.scan`` (no per-round
                           host sync; the default).
        driver="python" -- per-round jitted dispatch via ``run_round``.

        Cohort mode (``FedConfig.cohort_size`` < N) always drives rounds
        from the host — each round must sample a fresh cohort from the
        store — so both drivers collapse to the per-round loop there, and
        ``data`` must be a fleet object exposing ``cohort_arrays``."""
        if self.cohort_mode:
            for _ in range(rounds):
                self.run_round(
                    data, eval_set=eval_set, force_straggler=force_straggler
                )
            return self.history
        data = self._resident_data(data)
        if driver == "python":
            for _ in range(rounds):
                self.run_round(
                    data, eval_set=eval_set, force_straggler=force_straggler
                )
            return self.history
        force = None if force_straggler is None else jnp.asarray(force_straggler)
        self.state, outs = self.engine.run(
            self.state, data, rounds=rounds, eval_set=eval_set,
            force_straggler=force,
        )
        self._append(self._fetch(outs, rounds), rounds,
                     eval_set is not None)
        return self.history
