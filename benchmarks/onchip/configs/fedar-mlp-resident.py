"""The system under test and its reference, for ``fedar-mlp-resident``.

The harness finds this file beside the configuration's ``.json`` and uses
only what follows, so a configuration of another client family, engine,
aggregation or codec brings a file of its own:

* ``make_fleet(traffic, seed, spec)``: the fleet, drawn from the traffic
  file and the seed (``spec`` gives the sizes a sample needs, such as a
  vocabulary).  The harness reads two things of it, ``sizes`` (each
  client's samples; for a language model, its sequences) and
  ``num_clients``; the rest is for ``build`` and ``reference``;
* ``init_weights(seed, spec)``: the initial global model, any pytree of
  arrays, drawn from the seed;
* ``counts(spec)``: the operation and byte counts its per-layer readers
  take (``counting.Counts``);
* ``build(spec, fleet, weights)``: the program built for the cell, an
  object with ``round()`` -- the one call the window times; it returns the
  mask of clients whose samples went through local SGD --, ``checked(n)``
  -- the first ``n`` rounds through that same call, with what the check
  needs of each --, ``describe()`` (a dict for the log) and ``close()``,
  which drops the program's state before the reference runs;
* ``reference(fleet, spec, weights0, prog, precision="float32",
  fault=None)``: the reference's records of the same rounds
  (``fedref.Reference``);
* ``compare(prog, ref, weights0)``: the numbers ``correct`` compares.

Its ``.json`` may name ``phases`` (program phase scopes) and ``layers``
(layer -> op-name patterns) of its own, beside ``scopes.json``'s and
``layers.json``'s; this one has none.

Here the clients are the paper's MLP (``fleetgen``'s class-prototype
samples, He-initialised weights, ``counting``'s MLP counts) and the
program is ``FedARServer`` over the resident ``FedAREngine``: every
client's data and state on the device, one ``run_round`` a round.
"""
import functools

import numpy as np

import counting
import fedref
import fleetgen

compare = fedref.compare


def make_fleet(traffic: dict, seed: int, spec: dict):
    return fleetgen.make_fleet(traffic, seed)


def init_weights(seed: int, spec: dict):
    return fleetgen.init_weights(seed, spec["model"])


def counts(spec: dict) -> counting.Counts:
    model = spec["model"]
    return counting.Counts(
        counting.flops_per_sample_epoch(model),
        functools.partial(counting.local_sgd_work, model=model))


class Resident:
    """``FedARServer`` holding the benchmark's weights, resources and data."""

    def __init__(self, spec: dict, fleet, weights):
        import jax.numpy as jnp

        from repro import FedARServer, TaskRequirement
        from repro.common.config import FedConfig
        from repro.configs.fedar_mnist import MnistConfig
        from repro.core.engine import flatten
        from repro.core.resources import ResourceState
        from repro.data.datasets import FederatedDataset

        fed = FedConfig(num_clients=fleet.num_clients, **spec["fed"])
        server = FedARServer(MnistConfig(**spec["model"]), fed,
                             TaskRequirement(**spec["task"]), lr=spec["lr"])
        res = fleet.resources
        server.state = server.state._replace(
            params=flatten(weights),
            resources=ResourceState(*(jnp.asarray(res[k]) for k in
                                      ("memory", "bandwidth", "battery",
                                       "compute"))),
        )
        x, y, mask = fleet.dense()
        ragged = bool((fleet.sizes != fleet.sizes.max()).any())
        ds = FederatedDataset(
            name="onchip", x=x, y=y, sizes=fleet.sizes.astype(np.float32),
            activations=fleet.activations, mask=mask if ragged else None)
        self.data = server.engine.prepare_data(ds, layout=spec["layout"])
        del ds, x, y, mask
        self.eval_set = (jnp.asarray(fleet.eval_x), jnp.asarray(fleet.eval_y))
        self.server = server
        self.on_time = None

    def round(self):
        selected, self.on_time = self.server.run_round(
            self.data, eval_set=self.eval_set)
        return selected

    def checked(self, rounds: int) -> list:
        """Drive the first rounds through ``round`` and keep what each
        produced: the program's decisions, the trust it started from, its
        model, eval loss and trust after the round, and after the first
        round its defense history and error-feedback residual."""
        server = self.server
        recs = []
        score = np.asarray(server.state.trust.score)
        for r in range(rounds):
            selected = self.round()
            recs.append({
                "selected": np.asarray(selected, bool),
                "on_time": np.asarray(self.on_time, bool),
                "score_prev": score,
                "params": {k: np.asarray(v, np.float32)
                           for k, v in server.params.items()},
                "loss": float(server.history["loss"][-1]),
                "trust": np.asarray(server.history["trust"][-1]),
            })
            score = recs[-1]["trust"]
            if r == 0:
                # copied to the host, and no reference to the round's state
                # kept: the later rounds' device peak stays the program's own
                residual = server.state.compress_residual
                recs[0]["history"] = np.asarray(server.state.fg_history,
                                                np.float32)
                recs[0]["residual"] = (np.asarray(residual, np.float32)
                                       if residual.shape[1] else None)
                del residual
        return recs

    def describe(self) -> dict:
        return {"routes": self.server.engine.kernel_routes(),
                "layout": "packed" if "packed" in self.data else "dense"}

    def close(self):
        del self.server, self.data, self.eval_set


def build(spec: dict, fleet, weights) -> Resident:
    return Resident(spec, fleet, weights)


def reference(fleet, spec: dict, weights0: dict, prog: list, *,
              precision: str = "float32", fault=None) -> list:
    """The reference over the program's rounds, given each round's
    decisions and the trust it started from.  The first time, the first
    round's defense history and residual become each client's uplink
    (``fedref.uplink_rows``)."""
    first = prog[0]
    if "uplink" not in first:
        first["uplink"] = fedref.uplink_rows(first.pop("history"),
                                             first.pop("residual"), spec)
    ref = fedref.Reference(fleet, spec, weights0, precision=precision,
                           fault=fault)
    return [ref.round(r["selected"], r["on_time"], r["score_prev"])
            for r in prog]
