"""The one traffic generator: a fleet of clients from a traffic file and a seed.

A traffic file (``traffic/<name>.json``) describes the fleet; this module
turns it into arrays.  What sets the amount of work -- how many clients,
how many samples each, their hidden activation, which are poisoners or
resource-starved, and their resources -- depends on the file alone, so
every seed runs the same work.  The seed draws what the work is done on:
the sample values, the labels and the initial weights.  ``layout`` is the
seed-independent part alone, on which a configuration with samples of
another kind (token sequences, say) draws its own; ``make_fleet`` and
``init_weights`` draw the MLP's (``configs/fedar-mlp-resident.py``).

Two ways to describe clients:

* ``"profiles"``: one ``[labels, activation, samples]`` row per client
  (the paper's Table II robots);
* ``"sizes"``: a size distribution -- ``{"kind": "lognormal", "mean",
  "sigma", "min", "max"}`` -- read at evenly spaced quantiles, so the
  multiset of sizes is fixed, and spread over the clients by
  ``fleet_seed``.  Labels are drawn uniformly from all classes.

Samples are class prototypes plus Gaussian noise (a digit-like, learnable
task); a poisoner's labels are flipped with probability ``flip_frac``.
Nothing here imports the program: the same arrays feed the system under
test and the reference.
"""
from __future__ import annotations

import statistics
from dataclasses import dataclass
from typing import NamedTuple

import numpy as np

INPUT_DIM = 784
NUM_CLASSES = 10
NOISE = 1.5  # sample noise against unit-variance class prototypes


@dataclass
class Fleet:
    """Every client's samples, stored flat: client ``i`` owns rows
    ``offsets[i] : offsets[i] + sizes[i]`` of ``x`` and ``y``."""

    x: np.ndarray  # (S, 784) float32
    y: np.ndarray  # (S,) int32
    sizes: np.ndarray  # (N,) int64
    offsets: np.ndarray  # (N,) int64
    activations: np.ndarray  # (N,) int32, 0 = ReLU, 1 = softmax
    resources: dict  # memory, bandwidth, battery, compute: (N,) float32
    eval_x: np.ndarray  # (E, 784) float32
    eval_y: np.ndarray  # (E,) int32

    @property
    def num_clients(self) -> int:
        return int(self.sizes.shape[0])

    def dense(self):
        """``(x, y, mask)`` as an ``(N, n_max, ...)`` rectangle, each
        client's samples a prefix of its row.  The zero fill is lazily
        mapped memory: only the real samples are written."""
        n, n_max = self.num_clients, int(self.sizes.max())
        x = np.zeros((n, n_max, INPUT_DIM), np.float32)
        y = np.zeros((n, n_max), np.int32)
        mask = np.zeros((n, n_max), bool)
        for i, (o, s) in enumerate(zip(self.offsets, self.sizes)):
            x[i, :s] = self.x[o:o + s]
            y[i, :s] = self.y[o:o + s]
            mask[i, :s] = True
        return x, y, mask


def quantile_sizes(spec: dict, n: int) -> np.ndarray:
    """``n`` client sizes read from a lognormal at the quantiles
    ``(i + 0.5) / n``, clipped to ``[min, max]`` and rescaled so their mean
    is ``mean``: the same multiset for every seed."""
    if spec["kind"] != "lognormal":
        raise ValueError(f"unknown size distribution {spec['kind']!r}")
    sigma = float(spec["sigma"])
    norm = statistics.NormalDist()
    z = np.array([norm.inv_cdf((i + 0.5) / n) for i in range(n)])
    raw = np.exp(sigma * z)
    lo, hi, mean = int(spec["min"]), int(spec["max"]), float(spec["mean"])
    scale = mean / raw.mean()
    for _ in range(50):  # clipping moves the mean; settle the scale
        sizes = np.clip(np.rint(raw * scale), lo, hi)
        scale *= mean / sizes.mean()
    return np.clip(np.rint(raw * scale), lo, hi).astype(np.int64)


def _resources(n: int, starved: np.ndarray,
               rng: np.random.Generator) -> dict:
    """Per-client (memory MB, bandwidth MB/s, battery, compute MFLOP/s) as
    in the paper's section IV.A: most robots well resourced, the starved
    ones short of memory, bandwidth, battery and compute."""
    rows = {
        "memory": rng.uniform(128, 1024, n),
        "bandwidth": rng.uniform(1.0, 8.0, n),
        "battery": rng.uniform(0.6, 1.0, n),
        "compute": rng.uniform(50, 400, n),
    }
    k = int(starved.sum())
    rows["memory"][starved] = rng.uniform(16, 72, k)
    rows["bandwidth"][starved] = rng.uniform(0.05, 0.4, k)
    rows["battery"][starved] = rng.uniform(0.1, 0.3, k)
    rows["compute"][starved] = rng.uniform(5, 30, k)
    return {key: v.astype(np.float32) for key, v in rows.items()}


class Layout(NamedTuple):
    """The seed-independent part of a fleet, one entry per client.  Any
    configuration's fleet may be drawn on it: ``sizes`` counts whatever its
    samples are (MLP rows, token sequences)."""

    sizes: np.ndarray  # (N,) int64
    labels: list  # the client's label set, or None for all classes
    activations: np.ndarray  # (N,) int32, 0 = ReLU, 1 = softmax
    poison: np.ndarray  # (N,) bool
    starved: np.ndarray  # (N,) bool
    resources: dict  # memory, bandwidth, battery, compute: (N,) float32


def layout(traffic: dict) -> Layout:
    """The fleet's layout that the traffic file fixes, whatever the seed."""
    rng = np.random.default_rng(int(traffic.get("fleet_seed", 0)))
    if "profiles" in traffic:
        prof = traffic["profiles"]
        n = len(prof)
        sizes = np.array([int(p[2]) for p in prof], np.int64)
        labels = [list(p[0]) for p in prof]
        acts = np.array([int(p[1]) for p in prof], np.int32)
        poison = np.isin(np.arange(n), traffic.get("poisoners", []))
        starved = np.isin(np.arange(n), traffic.get("starved", []))
    else:
        n = int(traffic["clients"])
        sizes = rng.permutation(quantile_sizes(traffic["sizes"], n))
        labels = [None] * n
        acts = (rng.permutation(n) < round(n * traffic["softmax_share"])
                ).astype(np.int32)
        order = rng.permutation(n)
        n_poison = round(n * traffic.get("poison_share", 0.0))
        n_starved = round(n * traffic.get("starved_share", 0.0))
        poison = np.isin(np.arange(n), order[:n_poison])
        starved = np.isin(np.arange(n), order[n_poison:n_poison + n_starved])
    return Layout(sizes, labels, acts, poison, starved,
                  _resources(n, starved, rng))


def _draw(key, owner, classes, counts, poison, flip, n_eval):
    """Samples and labels for every stored row (``owner`` is the row's
    client) and for the eval set, on the device: labels uniform over the
    owner's classes, a poisoner's flipped with probability ``flip``,
    features a unit-variance class prototype plus noise."""
    import jax
    import jax.numpy as jnp

    kp, kc, kf, ks, kn, ke, kx = jax.random.split(key, 7)
    protos = jax.random.normal(kp, (NUM_CLASSES, INPUT_DIM))
    pick = jnp.floor(jax.random.uniform(kc, owner.shape)
                     * counts[owner]).astype(jnp.int32)
    y = classes[owner, pick]
    hit = poison[owner] & (jax.random.uniform(kf, owner.shape) < flip)
    shift = jax.random.randint(ks, owner.shape, 1, NUM_CLASSES)
    y = jnp.where(hit, (y + shift) % NUM_CLASSES, y)
    x = protos[y] + NOISE * jax.random.normal(kn, (owner.shape[0],
                                                   INPUT_DIM))
    ey = jax.random.randint(ke, (n_eval,), 0, NUM_CLASSES)
    ex = protos[ey] + NOISE * jax.random.normal(kx, (n_eval, INPUT_DIM))
    return x, y, ex, ey


def seed_key(seed: int):
    """A PRNG key from any non-negative seed below 2**62."""
    import jax

    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def make_fleet(traffic: dict, seed: int) -> Fleet:
    """The fleet a traffic file describes, its samples drawn on the device
    in one jitted call from ``seed`` (any non-negative integer)."""
    import jax
    import jax.numpy as jnp

    sizes, labels, acts, poison, _, res = layout(traffic)
    n = len(sizes)
    classes = np.zeros((n, NUM_CLASSES), np.int32)
    counts = np.zeros(n, np.float32)
    for i, c in enumerate(labels):
        c = list(range(NUM_CLASSES)) if c is None else c
        classes[i, :len(c)] = c
        counts[i] = len(c)
    owner = np.repeat(np.arange(n, dtype=np.int32), sizes)
    draw = jax.jit(_draw, static_argnums=6)
    x, y, ex, ey = draw(
        jax.random.fold_in(seed_key(seed), 1), jnp.asarray(owner),
        jnp.asarray(classes), jnp.asarray(counts), jnp.asarray(poison),
        np.float32(traffic.get("flip_frac", 0.0)),
        int(traffic["eval_samples"]))
    offsets = np.concatenate([[0], np.cumsum(sizes)[:-1]]).astype(np.int64)
    return Fleet(x=np.asarray(x, np.float32), y=np.asarray(y, np.int32),
                 sizes=sizes, offsets=offsets, activations=acts,
                 resources=res, eval_x=np.asarray(ex, np.float32),
                 eval_y=np.asarray(ey, np.int32))


def init_weights(seed: int, model: dict):
    """The initial global model, made on the device in one jitted call from
    ``seed`` (He-scaled normal weights, zero biases), as float32 leaves
    keyed like the paper's MLP."""
    import jax
    import jax.numpy as jnp

    d_in, hid, cls = model["input_dim"], model["hidden"], model["num_classes"]

    @jax.jit
    def make(key):
        k1, k2 = jax.random.split(key)
        return {
            "b1": jnp.zeros((hid,), jnp.float32),
            "b2": jnp.zeros((cls,), jnp.float32),
            "w1": jax.random.normal(k1, (d_in, hid)) * (2.0 / d_in) ** 0.5,
            "w2": jax.random.normal(k2, (hid, cls)) * (2.0 / hid) ** 0.5,
        }

    return make(jax.random.fold_in(seed_key(seed), 2))
