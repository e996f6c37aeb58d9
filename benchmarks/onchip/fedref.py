"""A plain reference of the FedAR round, and the comparison that decides
``correct``.

The reference follows the paper (arXiv:2101.03705, Algorithms 1 and 2) and
the configuration file, in straightforward ``jax.numpy``: local SGD of each
selected client over its own samples, the top-k uplink with error
feedback, the non-finite quarantine, the deviation ban, the FoolsGold
weights (dense, or over a count sketch), the trust update, the battery
drain and the weighted aggregation.  It imports nothing of the program and
takes nothing the program made: its fleet and initial weights come from
``fleetgen`` and the seed.  What it does take from each of the program's
rounds are the round's decisions -- which clients were selected and which
arrived before the timeout, both drawn by the program's simulator -- and
the trust scores the round started from.  It checks the selection against
those scores, and recomputes everything else.

``precision="float32"`` runs every matmul at ``HIGHEST`` precision.  The
configuration states float32 parameters, data and SGD arithmetic, with
matmul operands in bfloat16 (the MXU's default single pass) and float32
accumulation; each of the two controls puts one of those one step lower.
``precision="fp8"`` rounds the matmul operands to float8 e4m3 and keeps
everything else in float32; ``precision="bfloat16"`` holds the
parameters, the data, the updates and every product in bfloat16.
``fault`` plants one of the faults the comparison must catch, in the
reference put in the program's place (``half_clients``: half of the
contributing clients left out, the mean taken over the rest;
``client_flipped``: one contributing client's uplink negated).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

LEAVES = ("b1", "b2", "w1", "w2")  # the flat layout's order
# what the reference holds its state in, at each matmul precision
STORAGE = {"float32": jnp.float32, "bfloat16": jnp.bfloat16,
           "fp8": jnp.float32}
SKETCH_SALT = 0x5EED  # the sketch tables are drawn from seed + salt
CHUNK_ROWS = 256  # clients per local-SGD call
BAN_MARGIN = 0.05  # deviation bans within 5% of the threshold may flip
F8_MAX = 448.0  # float8 e4m3's largest finite value


def leaf_shapes(model: dict) -> dict:
    d, h, c = model["input_dim"], model["hidden"], model["num_classes"]
    return {"b1": (h,), "b2": (c,), "w1": (d, h), "w2": (h, c)}


def to_flat(p: dict):
    return jnp.concatenate([p[k].reshape(-1) for k in LEAVES])


def to_leaves(flat, model: dict) -> dict:
    out, off = {}, 0
    for k, shape in leaf_shapes(model).items():
        n = int(np.prod(shape))
        out[k] = flat[off:off + n].reshape(shape)
        off += n
    return out


def _fp8(a):
    """``a`` rounded to float8 e4m3 after scaling its largest magnitude to
    e4m3's largest value, as float8 matmuls scale their operands.  The
    rounding is passed over by the gradient, which flows as if through
    ``a``, and the scale is a constant."""
    scale = jax.lax.stop_gradient(jnp.max(jnp.abs(a)) / F8_MAX)
    scale = jnp.where(scale > 0, scale, 1.0)
    q = (a / scale).astype(jnp.float8_e4m3fn).astype(a.dtype) * scale
    return a + jax.lax.stop_gradient(q - a)


def matmul_for(precision: str):
    """The reference's matrix product at ``precision``: ``float32`` at
    ``HIGHEST``; ``bfloat16`` operands and result; or ``fp8``: operands
    rounded to float8 (``_fp8``), products and sums in float32."""
    if precision == "float32":
        return functools.partial(jnp.matmul,
                                 precision=jax.lax.Precision.HIGHEST)
    if precision == "bfloat16":
        return jnp.matmul
    if precision == "fp8":
        return lambda a, b: jnp.matmul(_fp8(a), _fp8(b),
                                       precision=jax.lax.Precision.HIGHEST)
    raise ValueError(f"unknown precision {precision!r}")


def _loss(p, x, y, m, act, mm):
    h = mm(x, p["w1"]) + p["b1"]
    h = jnp.where(act == 1, jax.nn.softmax(h, axis=-1), jax.nn.relu(h))
    lg = mm(h, p["w2"]) + p["b2"]
    ce = (jax.nn.logsumexp(lg, axis=-1)
          - jnp.take_along_axis(lg, y[:, None], axis=-1)[:, 0])
    return jnp.sum(ce * m) / jnp.maximum(jnp.sum(m), 1.0)


@functools.lru_cache(maxsize=None)
def _chunk_sgd(nb: int, batch: int, epochs: int, lr: float, precision: str):
    """Local SGD of one chunk of clients: each runs ``epochs`` passes over
    its ``nb`` batches in order; a batch's loss is the mean over its real
    samples, and an all-padding batch is a no-op."""
    mm = matmul_for(precision)
    dtype = STORAGE[precision]
    grad = jax.grad(_loss)

    def client(p, x, y, m, act):
        xb = x.reshape(nb, batch, -1)
        yb, mb = y.reshape(nb, batch), m.reshape(nb, batch)

        def step(p, b):
            g = grad(p, *b, act, mm)
            return jax.tree.map(lambda a, ga: a - lr * ga, p, g), None

        def epoch(p, _):
            return jax.lax.scan(step, p, (xb, yb, mb))[0], None

        return jax.lax.scan(epoch, p, None, length=epochs)[0]

    @jax.jit
    def run(p, xs, ys, gidx, mask, act):
        x, y, m = xs[gidx], ys[gidx], mask.astype(dtype)
        new = jax.vmap(client, in_axes=(None, 0, 0, 0, 0))(p, x, y, m, act)
        return jnp.concatenate(
            [new[k].reshape(new[k].shape[0], -1) for k in LEAVES], axis=1)

    return run


def _sketch_tables(seed: int, dim: int, r: int):
    rng = np.random.default_rng(seed + SKETCH_SALT)
    bucket = rng.integers(0, r, dim)
    sign = rng.choice(np.float32([-1.0, 1.0]), dim)
    return jnp.asarray(bucket, jnp.int32), sign


def _sketch(rows, bucket, sign, r: int):
    """(n, D) -> (n, r) count sketch: coordinate d adds ``sign[d] *
    rows[:, d]`` into bucket ``bucket[d]``."""
    return jax.ops.segment_sum((rows * sign[None, :]).T, bucket,
                               num_segments=r).T


def _similarity(hist, active, mm):
    n = hist.shape[0]
    unit = hist / jnp.maximum(jnp.linalg.norm(hist, axis=1, keepdims=True),
                              1e-9)
    cs = mm(unit, unit.T).astype(unit.dtype) - jnp.eye(n, dtype=unit.dtype)
    return jnp.where(active[:, None] & active[None, :], cs, -1.0)


def foolsgold_weights(hist, active, mm):
    """Fung et al.: max cosine over the cumulative updates, pardoning,
    logit re-scaling at kappa 0.5."""
    cs = _similarity(hist, active, mm)
    v = jnp.max(cs, axis=1)
    ratio = v[:, None] / jnp.maximum(v[None, :], 1e-9)
    cs = jnp.where(v[None, :] > v[:, None], cs * ratio, cs)
    wv = jnp.clip(1.0 - jnp.max(cs, axis=1), 0.0, 0.99)
    wv = jnp.clip(jnp.log(wv / jnp.maximum(1.0 - wv, 1e-9) + 1e-9) + 0.5,
                  0.0, 1.0)
    return jnp.where(active, wv, 0.0)


def cluster_weights(hist, active, mm, fed):
    """Cluster-aware weights: multiplicity ``1 + sum relu(cs)^power``,
    full weight up to ``slack`` times the active median, then decaying
    with ``sharpness``."""
    cs = _similarity(hist, active, mm)
    m = 1.0 + jnp.sum(jnp.clip(cs, 0.0, 1.0) ** fed["defense_cluster_power"],
                      axis=1)
    med = jnp.nan_to_num(jnp.nanmedian(jnp.where(active, m, jnp.nan)),
                         nan=1.0)
    wv = jnp.clip(fed["defense_cluster_slack"] * med / jnp.maximum(m, 1.0),
                  0.0, 1.0) ** fed["defense_cluster_sharpness"]
    return jnp.where(active, wv, 0.0)


def deviation(dec, screen, gamma):
    """Ban a screened client whose distance from the screened mean exceeds
    the population's mean distance by ``gamma`` standard deviations.
    Returns the ban and each client's distance over the threshold."""
    w = screen.astype(dec.dtype)[:, None]
    mean = jnp.sum(dec * w, axis=0) / jnp.maximum(jnp.sum(w), 1.0)
    dist = jnp.linalg.norm(dec - mean, axis=1)
    act = jnp.where(screen, dist, jnp.nan)
    mu = jnp.nanmean(act)
    sd = jnp.sqrt(jnp.nanmean((act - mu) ** 2) + 1e-12)
    thr = mu + gamma * sd
    return screen & (dist > thr), dist / thr


def check_resource(res: dict, task: dict):
    return ((res["memory"] >= task["memory"])
            & (res["bandwidth"] >= task["bandwidth"])
            & (res["battery"] >= task["battery"]) & (res["battery"] > 0.0))


def headroom(res: dict, task: dict):
    return (np.minimum(res["memory"] / task["memory"], 4.0)
            + np.minimum(res["bandwidth"] / task["bandwidth"], 4.0)
            + np.minimum(res["battery"] / max(task["battery"], 1e-6), 4.0)
            ) / 3.0


def selection_faults(selected, score, ok, res, task, k: int) -> int:
    """Ways the program's selection breaks Algorithm 2 given the trust it
    started from: a selected client that fails CheckResource or the trust
    floor, a count other than ``min(k, eligible)``, or an eligible client
    left out whose sort key beats a selected one's by more than rounding."""
    key = score + np.float32(0.01) * headroom(res, task).astype(np.float32)
    bad = int(np.sum(selected & ~ok))
    bad += int(selected.sum() != min(k, int(ok.sum())))
    if selected.any():
        floor = key[selected].min()
        bad += int(np.sum(ok & ~selected & (key > floor + 1e-4)))
    return bad


def update_trust(score, parts, fails, fed, selected, on_time, deviated,
                 interested):
    """Algorithm 1 with Table I's constants."""
    ok = selected & on_time & ~deviated
    parts = parts + selected
    fails = fails + (selected & ~ok)
    rate = fails / np.maximum(parts, 1)
    late = np.where(rate < fed["penalty_band"], fed["c_penalty"],
                    np.where(rate < fed["blame_band"], fed["c_blame"],
                             fed["c_ban"]))
    delta = np.where(ok, fed["c_reward"], 0.0)
    delta = np.where(selected & ~on_time & ~deviated, late, delta)
    delta = np.where(selected & deviated, fed["c_ban"], delta)
    delta = np.where(interested & ~selected, fed["c_interested"], delta)
    return (score + delta).astype(np.float32), parts, fails


def uplink_rows(history, residual, spec: dict):
    """Each client's cumulative uplink as the defense sees it: its history
    row plus its error-feedback residual, projected like the history.
    Top-k's error feedback telescopes, so this is the sum of the client's
    raw updates, whichever coordinates rounding let through each round."""
    fed = spec["fed"]
    history = jnp.asarray(history, jnp.float32)
    if residual is None or residual.shape[1] == 0:
        return np.asarray(history)
    residual = jnp.asarray(residual, jnp.float32)
    if fed["defense"] == "foolsgold_sketch":
        r = history.shape[1]
        bucket, sign = _sketch_tables(fed["seed"], residual.shape[1], r)
        residual = _sketch(residual, bucket, jnp.asarray(sign), r)
    return np.asarray(history + residual)


class Reference:
    """The reference fleet state, advanced one round at a time."""

    def __init__(self, fleet, spec: dict, weights0: dict, *,
                 precision: str = "float32", fault: str | None = None):
        self.fleet, self.spec, self.fault = fleet, spec, fault
        self.fed, self.model = spec["fed"], spec["model"]
        self.precision = precision
        self.dt = jnp.dtype(STORAGE[precision])
        self.mm = matmul_for(precision)
        n = fleet.num_clients
        self.g = to_flat({k: jnp.asarray(weights0[k], self.dt)
                          for k in LEAVES})
        dim = int(self.g.shape[0])
        self.dim = dim
        self.defense = self.fed["defense"]
        self.compress = self.fed.get("compress", "none")
        if self.defense == "foolsgold_sketch":
            r = self.fed["defense_sketch_dim"]
            self.bucket, sign = _sketch_tables(self.fed["seed"], dim, r)
            self.sign = jnp.asarray(sign, self.dt)
            self.hist = jnp.zeros((n, r), self.dt)
        elif self.defense == "foolsgold":
            self.hist = jnp.zeros((n, dim), self.dt)
        else:
            self.hist = jnp.zeros((n, 0), self.dt)
        if self.compress == "topk":
            self.k = self.fed.get("compress_k") or max(1, dim // 32)
            self.residual = jnp.zeros((n, dim), self.dt)
        elif self.compress != "none":
            raise ValueError(f"no reference for compress={self.compress!r}")
        if self.fed["aggregation"] != "fedar":
            raise ValueError("the reference covers aggregation='fedar'")
        self.res = {k: v.copy() for k, v in fleet.resources.items()}
        self.parts = np.zeros(n, np.int64)
        self.fails = np.zeros(n, np.int64)
        b = self.fed["local_batch_size"]
        self.nb = -(-int(fleet.sizes.max()) // b)
        self.width = self.nb * b
        self.xs = jnp.asarray(fleet.x, self.dt)
        self.ys = jnp.asarray(fleet.y)
        self.sizes = jnp.asarray(fleet.sizes, self.dt)
        self.eval = (jnp.asarray(fleet.eval_x, self.dt),
                     jnp.asarray(fleet.eval_y))

    # -- local SGD --------------------------------------------------------
    def _locals(self, selected):
        """(N, D) flat local models: the selected clients' after local SGD,
        everyone else's the global model."""
        fleet, fed = self.fleet, self.fed
        n = fleet.num_clients
        rows = min(CHUNK_ROWS, n)
        sgd = _chunk_sgd(self.nb, fed["local_batch_size"],
                         fed["local_epochs"], float(self.spec["lr"]),
                         self.precision)
        p = to_leaves(self.g, self.model)
        out = jnp.broadcast_to(self.g, (n, self.dim))
        idx = np.flatnonzero(selected)
        pos = np.arange(self.width)
        last = fleet.x.shape[0] - 1
        for c in range(0, len(idx), rows):
            part = idx[c:c + rows]
            pad = np.concatenate([part, np.full(rows - len(part), part[0])])
            live = np.arange(rows) < len(part)
            gidx = np.minimum(fleet.offsets[pad][:, None] + pos[None, :], last)
            mask = (pos[None, :] < fleet.sizes[pad][:, None]) & live[:, None]
            new = sgd(p, self.xs, self.ys, jnp.asarray(gidx),
                      jnp.asarray(mask),
                      jnp.asarray(fleet.activations[pad]))
            out = out.at[jnp.asarray(part)].set(new[:len(part)])
        return out

    # -- the uplink ------------------------------------------------------
    def _topk(self, v):
        _, idx = jax.lax.top_k(jnp.abs(v), self.k)
        kept = jnp.take_along_axis(v, idx, axis=1)
        rows = jnp.arange(v.shape[0])[:, None]
        return jnp.zeros_like(v).at[rows, idx].set(kept)

    # -- one round -------------------------------------------------------
    def round(self, selected, on_time, score_prev):
        """One round, given the program's decisions and the trust scores
        it started from.  Returns the round's record."""
        fed, task = self.fed, self.spec["task"]
        selected = np.asarray(selected, bool)
        on_time = np.asarray(on_time, bool)
        score_prev = np.asarray(score_prev, np.float32)
        n = selected.shape[0]
        ok = check_resource(self.res, task) & (score_prev >= fed["min_trust"])
        k = max(1, int(n * fed["client_fraction"]))
        sel_faults = selection_faults(selected, score_prev, ok, self.res,
                                      task, k)

        deltas = self._locals(selected) - self.g[None, :]
        active = selected & on_time
        act_d = jnp.asarray(active)
        if self.compress == "topk":
            v = deltas + self.residual
            kept = self._topk(v)
            dec = jnp.where(act_d[:, None], kept, 0.0)
            self.residual = jnp.where(act_d[:, None], v - kept, self.residual)
        else:
            dec = jnp.where(jnp.asarray(selected)[:, None], deltas, 0.0)
        if self.fault == "client_flipped":
            first = int(np.flatnonzero(active)[0])
            dec = dec.at[first].multiply(-1.0)
        quarantined = ~jnp.all(jnp.isfinite(dec), axis=1)
        dec = jnp.where(quarantined[:, None], 0.0, dec)
        screen = act_d & ~quarantined
        deviated, ratio = deviation(dec, screen, fed["deviation_gamma"])
        deviated = deviated | (act_d & quarantined)
        # a ban this close to its threshold can flip on rounding
        near = np.asarray(screen & (jnp.abs(ratio - 1.0) < BAN_MARGIN))
        contributing = act_d & ~deviated
        if self.defense != "none":
            add = dec if self.defense == "foolsgold" else _sketch(
                dec, self.bucket, self.sign, self.hist.shape[1])
            self.hist = self.hist + jnp.where(contributing[:, None], add, 0.0)
            if self.defense == "foolsgold":
                fgw = foolsgold_weights(self.hist, contributing, self.mm)
            else:
                fgw = cluster_weights(self.hist, contributing, self.mm, fed)
            weights = self.sizes * fgw.astype(self.dt)
        else:
            weights = self.sizes
        w = jnp.where(contributing, weights, 0.0)
        if self.fault == "half_clients":
            w = jnp.where(jnp.cumsum(contributing) % 2 == 1, w, 0.0)
        num = self.mm(w, dec).astype(self.dt)
        self.g = self.g + num / jnp.maximum(jnp.sum(w), 1e-9)

        deviated_h = np.asarray(deviated)
        score, self.parts, self.fails = update_trust(
            score_prev, self.parts, self.fails, fed, selected, on_time,
            deviated_h, ok)
        cost = 0.02  # battery cost of one training round (section IV.A)
        self.res["battery"] = np.where(
            selected, np.maximum(self.res["battery"] - cost, 0.0),
            np.minimum(self.res["battery"] + cost / 4, 1.0)
        ).astype(np.float32)
        p = to_leaves(self.g, self.model)
        ex, ey = self.eval
        loss = _loss(p, ex, ey, jnp.ones(ex.shape[0], self.dt), 0, self.mm)
        return {
            "params": {k: np.asarray(v, np.float32) for k, v in p.items()},
            "loss": float(loss),
            "trust": score,
            "selection_faults": sel_faults,
            "near_ban": near,
            "uplink": uplink_rows(
                self.hist, self.residual if self.compress != "none" else None,
                self.spec),
        }


# -- the comparison --------------------------------------------------------
def _norm(a) -> float:
    return float(np.linalg.norm(np.asarray(a, np.float64)))


def leaf_gaps(prog: dict, ref: dict, skip=()):
    """Worst leaf of ``(norm gap, relative L2)``: ``| |p| - |r| |`` and
    ``|p - r|``, each over the larger of the leaf's reference norm and the
    median leaf's."""
    norms = {k: _norm(ref[k]) for k in LEAVES}
    med = float(np.median(list(norms.values())))
    gap = rel = 0.0
    for k in LEAVES:
        if k in skip:
            continue
        den = max(norms[k], med, 1e-30)
        gap = max(gap, abs(_norm(prog[k]) - norms[k]) / den)
        rel = max(rel, _norm(np.asarray(prog[k], np.float64) - ref[k]) / den)
    return gap, rel


def compare(prog: list, ref: list, params0: dict) -> dict:
    """The numbers ``correct`` compares, from the program's first three
    rounds (``prog``) and the reference's (``ref``): dicts with
    ``params``, ``loss``, ``trust``, ``uplink`` (round 1's at least) and,
    on the reference, ``selection_faults`` and ``near_ban``."""
    p0 = {k: np.asarray(params0[k], np.float64) for k in LEAVES}

    def change(rec):
        return {k: np.asarray(rec["params"][k], np.float64) - p0[k]
                for k in LEAVES}

    u_p, u_r = change(prog[0]), change(ref[0])
    norms = {k: _norm(u_r[k]) for k in LEAVES}
    med = float(np.median(list(norms.values())))
    # leaves whose reference update is nought to rounding move by round-off
    # alone on either side: left out by a rule on the reference's norm
    skip = tuple(k for k in LEAVES if norms[k] < 1e-3 * med)
    update_gap, update_rel = leaf_gaps(u_p, u_r, skip)
    change_gap, _ = leaf_gaps(change(prog[-1]), change(ref[-1]), skip)
    loss_gap = max(abs(a["loss"] - b["loss"]) / max(abs(b["loss"]), 1e-30)
                   for a, b in zip(prog, ref))
    # trust and the per-client uplinks are compared on every client but
    # those whose deviation ban came within BAN_MARGIN of its threshold
    near = np.zeros(prog[0]["trust"].shape, bool)
    trust_mismatch = 0
    for a, b in zip(prog, ref):
        near |= b["near_ban"]
        off = np.abs(np.asarray(a["trust"]) - b["trust"]) > 1e-3
        trust_mismatch += int(np.sum(off & ~near))
    # per client: one minus the cosine between the program's round-1
    # uplink and the reference's, worst client.  Round 1 starts every
    # client from the same model, so this is local SGD and the codec alone
    up, ur = (np.asarray(prog[0]["uplink"], np.float64),
              np.asarray(ref[0]["uplink"], np.float64))
    n_p, n_r = np.linalg.norm(up, axis=1), np.linalg.norm(ur, axis=1)
    rows = (n_r > 0) & ~ref[0]["near_ban"]
    client_cos = 0.0
    if rows.any():
        cos = np.sum(up * ur, axis=1)[rows] / np.maximum(
            n_p[rows] * n_r[rows], 1e-300)
        client_cos = float(np.max(1.0 - cos))
    return {
        "loss_gap": float(loss_gap),
        "update_norm_gap": float(update_gap),
        "change_norm_gap": float(change_gap),
        "update_rel_l2": float(update_rel),
        "client_cos_gap": client_cos,
        "trust_mismatch": trust_mismatch,
        "selection_faults": int(sum(b["selection_faults"] for b in ref)),
    }

