"""Fully-jitted multi-round FedAR engine (Algorithm 2 inside one XLA scan).

The seed reproduction drove communication rounds from a python ``for`` loop —
one dispatch per round plus host round-trips for trust/battery bookkeeping.
This engine runs R rounds inside a single ``jax.lax.scan``: client selection,
vmapped local SGD, virtual-latency straggler masking, deviation ban, FoolsGold
weighting, trust + battery updates and aggregation are all carried state, and
per-round histories come back as stacked scan outputs.  Nothing touches the
host until the whole run finishes, so the engine scales to fleets of
512-4096 clients instead of 12.

Scan-carry fields -> Algorithm 2 of the paper:

  ``EngineState.params``        global model w_i            (line 3 init,
                                                             line 14 update)
  ``EngineState.trust``         trust scores C_m + the participation /
                                failure counters Algorithm 1 reads
                                                            (lines 6-8, 15)
  ``EngineState.resources``     per-robot (M, B, E, F); battery E_m drains
                                with participation -> CheckResource input
                                                            (lines 6-7)
  ``EngineState.fg_history``    defense history block (``core/defense.py``:
                                dense (N, D) cumulative updates for
                                FoolsGold, count-sketched (N, r) for the
                                cluster-aware variant)  (line 13 weights)
  ``EngineState.pending_*``     buffered-async in-flight updates: a
                                fixed-size (one slot per client) buffer of
                                deltas with issue/arrival round tags; late
                                arrivals merge staleness-discounted instead
                                of being waited on            (lines 11-14,
                                                             no-wait variant)
  ``EngineState.round_idx``     the round counter i          (line 5 loop)

Per-round stacked outputs (``RoundOutputs``) carry the histories the paper's
figures need: post-update trust (Fig 7), the selected / on-time masks
(Fig 8), virtual round time, and eval loss/accuracy (Fig 6).

Mesh sharding (``FedConfig.mesh_shape > 1``): the whole scan body runs
inside a ``shard_map`` over a 1-D ``clients`` mesh (``core/distributed``).
Client-indexed *heavy* tensors — the stacked local datasets, the (N, D)
FoolsGold history and async delta buffer — shard into N/k client blocks
(``PartitionSpec(client_axis)``), so vmapped local SGD and the buffered
merge run data-parallel across devices; aggregation is a trust*staleness-
weighted ``psum`` of per-shard partial reductions.  The (N,) bookkeeping
vectors (trust, resources, masks, RNG draws) replicate, so selection's
global trust sort and Algorithm 1 stay bit-identical to the single-device
engine; only reduction order differs (fp32 tolerance).  With one device (or
``mesh_shape`` unset) the identity ``ClientComms`` reproduces the seed
numerics exactly.

Padding-free, selection-gated hot path: per-round compute tracks real
selected samples, not N * n_max.  ``data["packed"]`` (built by
``FederatedDataset.packed_arrays``) swaps the rectangular sample slab for
size-bucketed blocks — local SGD runs per bucket and a single inverse-
permutation gather restores canonical client order — while
``FedConfig.select_frac`` gates the SGD down to the statically-capped
selected cohort (unselected clients contribute exact zeros).  Both paths
are bit-identical (fp32) to the dense full-N vmap, so they compose freely
with every aggregation mode, defense and the mesh.

The hot aggregation path goes through the Pallas ``fedavg_agg`` kernel
(trust-weighted + staleness-decayed in one pass) when running on TPU
(``FedConfig.agg_impl``); local SGD itself routes through the fused Pallas
``local_sgd`` kernel (``FedConfig.sgd_impl``): one ``pallas_call`` runs
every client's whole masked epochs x batches loop, for the dense block and
for every packed bucket alike (``FedAREngine._client_update``).
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import NamedTuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.common import tracing
from repro.common.config import FedConfig
from repro.common.tracing import phase, span
from repro.configs.fedar_mnist import MnistConfig
from repro.core import aggregation as agg
from repro.core.compress import client_keys as compress_keys
from repro.core.compress import make_compression
from repro.core.defense import make_defense
from repro.core.faults import make_faults
from repro.core.distributed import (
    ClientComms,
    MeshComms,
    client_mesh,
    client_spec,
    packed_specs,
    replicated_spec,
    window_client_spec,
)
from repro.core.resources import (
    ResourceState,
    TaskRequirement,
    drain_battery,
    make_fleet,
    round_latency,
)
from repro.core.client_store import ClientStore
from repro.core.selection import sample_cohort, select_clients
from repro.core.trust import TrustState, init_trust, update_trust
from repro.kernels.ops import resolve_impl
from repro.models.client import ClientModel
from repro.models.mnist import MnistClientModel

# Domain separator for the per-round compression key: folded off the round
# key AFTER its pinned 3-way split (selection/latency/poison), so enabling
# compression never shifts the random stream the goldens pin.
_COMPRESS_KEY_FOLD = 0xC0DEC


def flatten(params) -> jnp.ndarray:
    """Param pytree -> flat (D,) aggregation-boundary vector.  Leaves
    concatenate in ``jax.tree.leaves`` order (dict keys sorted); mixed leaf
    dtypes promote to the widest float (``unflatten`` casts back)."""
    leaves = jax.tree.leaves(params)
    return jnp.concatenate([leaf.reshape(-1) for leaf in leaves])


def unflatten(flat, template):
    """Flat (D,) vector -> pytree shaped (and dtyped) like ``template``.
    The per-leaf ``astype`` restores low-precision leaves (bf16 round-trips
    exactly through the f32 flat view); float32 templates are untouched."""
    leaves, treedef = jax.tree.flatten(template)
    out, off = [], 0
    for leaf in leaves:
        n = int(np.prod(leaf.shape))
        out.append(flat[off : off + n].reshape(leaf.shape).astype(leaf.dtype))
        off += n
    return jax.tree.unflatten(treedef, out)


class EngineState(NamedTuple):
    """Scan carry — every piece of server state Algorithm 2 mutates."""

    params: jnp.ndarray  # (D,) flat global model
    trust: TrustState  # (N,) score / participations / failures
    resources: ResourceState  # (N,) memory / bandwidth / battery / compute
    fg_history: jnp.ndarray  # (N, d) defense history; d = D dense FoolsGold,
    #                          r sketched, 0 with the defense off
    pending_delta: jnp.ndarray  # (N, D) async buffer; (N, 0) unless async
    pending_weight: jnp.ndarray  # (N,) weight snapshot at issue time
    pending_issued: jnp.ndarray  # (N,) int32 round the update was computed
    pending_arrival: jnp.ndarray  # (N,) int32 round it lands at the server
    pending_valid: jnp.ndarray  # (N,) bool slot occupied
    compress_residual: jnp.ndarray  # (N, D) error-feedback residual;
    #                                 (N, 0) with compression off
    round_idx: jnp.ndarray  # () int32 communication round i


class RoundOutputs(NamedTuple):
    """Per-round history row, stacked over rounds by the scan."""

    trust: jnp.ndarray  # (N,) post-update trust scores
    selected: jnp.ndarray  # (N,) bool participant mask M_m
    on_time: jnp.ndarray  # (N,) bool arrived within timeout t
    round_time: jnp.ndarray  # () virtual seconds this round cost
    loss: jnp.ndarray  # () eval loss (nan when no eval set)
    acc: jnp.ndarray  # () eval accuracy (nan when no eval set)
    codec_rows_sent: jnp.ndarray  # () int32 rows the codec transmitted
    #                               (0 with compression off)


class FedAREngine:
    """Jit-compiled FedAR round engine over a simulated robot fleet.

    ``step``  — one communication round (jitted); the python-driver path.
    ``run``   — R rounds in one ``lax.scan`` (jitted once per R); no host
                sync until the final histories come back stacked.

    With ``FedConfig.mesh_shape > 1`` (and that many devices available) both
    entry points run the round body inside a ``shard_map`` over the
    ``clients`` mesh axis; the public API and the host-visible (N,)-shaped
    histories are unchanged.
    """

    def __init__(
        self,
        model: Union[ClientModel, MnistConfig],
        fed: FedConfig,
        req: TaskRequirement,
        *,
        lr: float = 0.1,
    ):
        # a bare MnistConfig keeps the paper-exact legacy constructor working
        if isinstance(model, MnistConfig):
            model = MnistClientModel(model)
        self.model = model
        self.cfg = getattr(model, "cfg", None)
        self.fed, self.req, self.lr = fed, req, lr
        # the local-SGD route, decided once, host-side, from the config
        self.sgd_route = self._decide_sgd_route()
        key = jax.random.PRNGKey(fed.seed)
        self.template = model.init(key)
        self.dim = flatten(self.template).shape[0]
        self.defense = make_defense(fed, self.dim)
        self.compression = make_compression(fed, self.dim)
        # rows the uplink codec encodes a round: every client's, sent or
        # not (RoundOutputs.codec_rows_sent counts the sent ones)
        self.codec_rows = fed.num_clients if self.compression.active else 0
        self.faults = make_faults(fed)
        self.resources0, self.poison_mask = make_fleet(
            fed.num_clients,
            num_starved=fed.num_starved,
            num_poisoners=fed.num_poisoners,
            seed=fed.seed,
        )
        self.mesh = client_mesh(fed)
        self.comms: ClientComms = (
            MeshComms(fed.client_axis, self.mesh.devices.size,
                      tree=fed.tree_reduce)
            if self.mesh is not None
            else ClientComms()
        )
        # selection-gated local SGD: static cohort cap C = ceil(frac * N).
        # C must cover the selection count k or selected updates would be
        # silently dropped (numerics depend on every selected delta).
        if fed.select_frac is not None:
            if not 0.0 < fed.select_frac <= 1.0:
                raise ValueError(
                    f"select_frac must be in (0, 1], got {fed.select_frac}"
                )
            self.cohort_cap = max(
                1, int(np.ceil(fed.select_frac * fed.num_clients))
            )
            k = max(1, int(fed.num_clients * fed.client_fraction))
            if self.cohort_cap < k:
                raise ValueError(
                    f"select_frac={fed.select_frac} caps the SGD cohort at "
                    f"C={self.cohort_cap} < the {k} clients selection can "
                    f"pick (client_fraction={fed.client_fraction}); raise "
                    f"select_frac to at least client_fraction"
                )
        else:
            self.cohort_cap = None
        self._step = jax.jit(self._step_fn, static_argnames=("train_flops",))
        self._run = jax.jit(
            self._run_fn, static_argnames=("rounds", "train_flops")
        )

    # ------------------------------------------------------------------
    def init_state(self) -> EngineState:
        N, D = self.fed.num_clients, self.dim
        fg_d = self.defense.history_dim(D)
        buf_d = D if self.fed.aggregation == "async" else 0
        res_d = self.compression.residual_dim(D)
        return EngineState(
            params=flatten(self.template),
            trust=init_trust(N, self.fed),
            resources=self.resources0,
            fg_history=jnp.zeros((N, fg_d)),
            pending_delta=jnp.zeros((N, buf_d)),
            pending_weight=jnp.zeros((N,)),
            pending_issued=jnp.zeros((N,), jnp.int32),
            pending_arrival=jnp.zeros((N,), jnp.int32),
            pending_valid=jnp.zeros((N,), bool),
            compress_residual=jnp.zeros((N, res_d)),
            round_idx=jnp.zeros((), jnp.int32),
        )

    # -------------------------------------------------- PartitionSpecs
    # Sharded leaves are the O(N*D) / O(N*samples) tensors; (N,) bookkeeping
    # replicates so global selection / trust math is bit-identical to the
    # single-device engine (O(N) bytes per device is noise next to the
    # O(N*D/k) blocks).
    def state_specs(self) -> EngineState:
        Pc, Pr = client_spec(self.fed), replicated_spec()
        return EngineState(
            params=Pr,
            trust=TrustState(Pr, Pr, Pr),
            resources=ResourceState(Pr, Pr, Pr, Pr),
            fg_history=Pc,
            pending_delta=Pc,
            pending_weight=Pr,
            pending_issued=Pr,
            pending_arrival=Pr,
            pending_valid=Pr,
            compress_residual=Pc,
            round_idx=Pr,
        )

    def data_specs(self, data=None) -> dict:
        """Specs for the engine's data dict.  The optional ragged-shard keys
        (``mask`` (N, n), ``round_mask`` (W, N, n) — see ``data/datasets``)
        shard their client axis like the sample arrays; pass ``data`` so the
        spec pytree matches the dict actually fed to the shard_map.  The
        bucketed packed layout (``FederatedDataset.packed_arrays``) swaps
        the dense sample rectangle for per-bucket arrays whose row axis
        shards over clients (``distributed.packed_specs``)."""
        Pc, Pr = client_spec(self.fed), replicated_spec()
        if data is not None and "packed" in data:
            return {
                "sizes": Pr,
                "activations": Pr,
                "packed": packed_specs(self.fed, data["packed"]),
            }
        specs = {k: Pc for k in self.model.data_keys}
        specs["sizes"] = Pr
        if data is not None:
            if "mask" in data:
                specs["mask"] = Pc
            if "round_mask" in data:
                specs["round_mask"] = window_client_spec(self.fed)
            if "cohort_valid" in data:
                # host-side preselection mask: (K,) bookkeeping, replicated
                # like the selection mask it replaces
                specs["cohort_valid"] = Pr
        return specs

    def _round_out_specs(self) -> RoundOutputs:
        return RoundOutputs(*[replicated_spec()] * len(RoundOutputs._fields))

    def _in_specs(self, data, eval_set, force_straggler):
        Pr = replicated_spec()
        return (
            self.state_specs(),
            self.data_specs(data),
            None
            if eval_set is None
            else jax.tree.map(lambda _: Pr, eval_set),
            None if force_straggler is None else Pr,
        )

    def _placed(self, state, data, eval_set, force_straggler):
        """The round inputs laid out on the mesh as the sharded program
        takes them (as they are, off the mesh).  A jit compiles once per
        input layout: an unplaced first state and the sharded state the
        round returns would compile the round twice, and unplaced data
        would be scattered from one device every round.  Inputs already in
        place are not copied."""
        args = (state, data, eval_set, force_straggler)
        if self.mesh is None:
            return args
        return self._on_mesh(
            args, self._in_specs(data, eval_set, force_straggler)
        )

    def _on_mesh(self, tree, specs):
        """``tree`` laid out on the mesh by its PartitionSpecs."""
        shardings = jax.tree.map(
            lambda spec: NamedSharding(self.mesh, spec), specs,
            is_leaf=lambda x: isinstance(x, P),
        )
        return jax.device_put(tree, shardings)

    # ---------------------------------------------------- ClientUpdate
    def _client_update(self, g_flat, data, selected, round_idx):
        """ClientUpdate (Algorithm 2 lines 16-21) over this shard's clients
        -> ``(locals_flat, locals_c, cohort)``: the canonical (N_loc, D)
        post-SGD flat params, plus — with ``select_frac`` set — the compact
        cohort rows and their ``(canon, valid)`` map, so deviation and
        aggregation can skip the known-zero rows (``None, None`` ungated).

        Every layout reaches local SGD through ``_ragged_block_sgd`` as a
        list of ``(fields, sample_mask)`` blocks: the dense sample block
        (or its gated cohort) is one block; the packed layout
        (``FederatedDataset.packed_arrays``) gives one block per size
        bucket — cost tracks the bucket widths (<= 2x the real samples)
        instead of N * n_max — restored to canonical order by one gather
        through the precomputed inverse permutation (dummy pad rows carry
        an all-False mask, and ``inv`` never points at them), or gated
        through the two-pass global cohort (``_packed_gated_locals``).
        Gated, unselected clients gather the untouched global params, so
        their delta is exactly zero."""
        sel_loc = self.comms.local(selected)
        if "packed" in data:
            packed = data["packed"]
            if self.cohort_cap is None:
                blocks = [
                    (dict(zip(self.model.data_keys,
                              (packed["x"][b], packed["y"][b],
                               packed["act"][b]))),
                     self._bucket_mask(packed, b, round_idx))
                    for b in range(len(packed["x"]))
                ]
                locals_cat = self._ragged_block_sgd(g_flat, blocks)
                return locals_cat[packed["inv"]], None, None
            locals_c, cohort = self._packed_gated_locals(
                g_flat, packed, sel_loc, round_idx
            )
        else:
            fields = {k: data[k] for k in self.model.data_keys}
            mask = self._round_window(data.get("mask"),
                                      data.get("round_mask"), round_idx)
            if self.cohort_cap is None:
                return (self._ragged_block_sgd(g_flat, [(fields, mask)]),
                        None, None)
            # stable argsort: selected rows first, in canonical order, up to
            # the static cohort cap
            cap = min(sel_loc.shape[0], self.cohort_cap)
            idx = jnp.argsort(jnp.where(sel_loc, 0, 1))[:cap]
            fields_c = {k: v[idx] for k, v in fields.items()}
            m_c = None if mask is None else mask[idx]
            locals_c = self._ragged_block_sgd(g_flat, [(fields_c, m_c)])
            cohort = (idx, sel_loc[idx])
        locals_flat = self._expand_cohort(
            locals_c, cohort[0], cohort[1], sel_loc.shape[0], g_flat
        )
        return locals_flat, locals_c, cohort

    def _ragged_block_sgd(self, g_flat, blocks):
        """Local SGD over a list of rectangular ``(fields, sample_mask)``
        client blocks, of any widths -> concatenated (sum rows, D) flat
        local params, in block order.  On the ``"fused_ragged"`` route the
        model's ``fused_ragged_update`` runs ALL blocks inside ONE
        ragged-grid ``pallas_call``; the XLA route keeps one vmap per block
        (``_block_sgd``; XLA cannot fuse across the differing widths)."""
        if self.sgd_route == "fused_ragged":
            return self.model.fused_ragged_update(
                g_flat, blocks, lr=self.lr,
                batch_size=self.fed.local_batch_size,
                epochs=self.fed.local_epochs,
            )
        return jnp.concatenate(
            [self._block_sgd(g_flat, f, m) for f, m in blocks]
        )

    def _block_sgd(self, g_flat, fields, m):
        """The XLA route's local SGD over one block of clients -> stacked
        flat local params (rows, D): the model's ``client_update`` (the
        seed-exact reference) vmapped over the block.  ``fields`` is the
        dict of stacked per-client sample arrays keyed by
        ``self.model.data_keys`` (client axis leading)."""
        fed = self.fed

        def client_update(p_flat, f, m=None):
            p = unflatten(p_flat, self.template)
            new = self.model.client_update(
                p,
                f,
                lr=self.lr,
                batch_size=fed.local_batch_size,
                epochs=fed.local_epochs,
                sample_mask=m,
            )
            return flatten(new)

        if jax.tree.leaves(fields)[0].shape[0] == 1:
            # XLA lowers a size-1 vmap without its batch axis, which
            # reorders the per-batch gradient sums by an ulp; run a lone
            # row as a batch of two so it matches the same client inside
            # any wider block bit for bit (packed == dense)
            fields, m = jax.tree.map(
                lambda v: jnp.concatenate([v, v]), (fields, m)
            )
            return self._block_sgd(g_flat, fields, m)[:1]
        if m is None:
            return jax.vmap(client_update, in_axes=(None, 0))(g_flat, fields)
        return jax.vmap(client_update, in_axes=(None, 0, 0))(
            g_flat, fields, m
        )

    @staticmethod
    def _expand_cohort(vals, canon, valid, rows, fill_row):
        """(cap, D) cohort rows -> (rows, D) canonical block: one int32
        scatter builds the canonical->cohort-slot map (invalid slots drop,
        unmapped clients point at the appended ``fill_row``), then one row
        gather restores canonical order — no (rows, D) zero-buffer +
        scatter-add chain on the hot path."""
        cap = vals.shape[0]
        aug = jnp.concatenate([vals, fill_row[None, :]])
        inv = jnp.full((rows,), cap, jnp.int32).at[
            jnp.where(valid, canon, rows)
        ].set(jnp.arange(cap, dtype=jnp.int32), mode="drop")
        return aug[inv]

    @staticmethod
    def _round_window(mask, round_mask, round_idx):
        """This round's effective sample mask: the static ``mask`` & the
        drift schedule's active window ``round_mask[round_idx mod W]``
        (either may be ``None``)."""
        if round_mask is None:
            return mask
        win = jax.lax.dynamic_index_in_dim(
            round_mask, jnp.remainder(round_idx, round_mask.shape[0]), 0,
            keepdims=False,
        )
        return win if mask is None else mask & win

    def _bucket_mask(self, packed, b, round_idx):
        """Packed bucket ``b``'s sample mask for this round."""
        rm = packed.get("round_mask")
        return self._round_window(packed["mask"][b],
                                  None if rm is None else rm[b], round_idx)

    @staticmethod
    def _desc_order(packed) -> list:
        """Bucket indices sorted widest-first — the order the two-pass
        cohort walks (and the flat sample views concatenate in)."""
        return sorted(
            range(len(packed["x"])),
            key=lambda b: -packed["x"][b].shape[1],
        )

    def _with_flat_packed(self, data):
        """Hoist the loop-invariant, descending-width flat sample views
        out of the round scan: the two-pass gated gather addresses samples
        through one flat (S_loc, dim) buffer; rebuilding that concat every
        round would put a copy of the whole sample set on the hot path.
        Called by both entry points after entering ``shard_map`` (the views
        are shard-local) but before the scan body."""
        if "packed" not in data or self.cohort_cap is None:
            return data
        packed = dict(data["packed"])
        desc = self._desc_order(packed)
        dim = packed["x"][0].shape[2]
        packed["flat"] = (
            jnp.concatenate(
                [packed["x"][b].reshape(-1, dim) for b in desc]
            ),
            jnp.concatenate([packed["y"][b].reshape(-1) for b in desc]),
        )
        out = dict(data)
        out["packed"] = packed
        return out

    def _packed_cohort_plan(self, widths, rows) -> list:
        """Static slot plan of the two-pass global cohort: ONE allocation
        of ``min(cohort_cap, sum rows)`` slots across all buckets, widest
        bucket first — not per-bucket ``min(rows_b, C)`` caps that sum
        toward N.  Soundness: at most C clients are selected per shard and
        slots are granted widest-first, so the j-th widest selected row
        always lands on a slot at least as wide as its own bucket."""
        plan, remaining = [], self.cohort_cap
        for b in sorted(range(len(widths)), key=lambda i: -widths[i]):
            take = min(rows[b], remaining)
            if take > 0:
                plan.append((b, take))
                remaining -= take
        return plan

    def _packed_gated_locals(self, g_flat, packed, sel_loc, round_idx):
        """Two-pass selection-gated ClientUpdate over the packed layout.

        Pass 1 (global count): ONE stable argsort over every row of every
        bucket, keyed selected-first — rows arrive bucket-descending, so
        the selected prefix is ordered widest-first.  Pass 2 (one capped
        gather): the static slot plan (``_packed_cohort_plan``) slices that
        prefix into per-width slot groups and gathers each group's samples
        from the flat descending-width buffer ``packed["flat"]``
        (``_with_flat_packed``; clamped reads past a row's own storage are
        masked off, and a narrower client inside a wider slot just runs
        extra all-masked batches — exact no-ops), so gated compute tracks
        the top-C bucket widths instead of summing per-bucket caps toward
        N.  Returns ``(locals_c, cohort)``."""
        desc = self._desc_order(packed)
        widths = [packed["x"][b].shape[1] for b in desc]
        rows = [packed["x"][b].shape[0] for b in desc]
        perm_d = jnp.concatenate([packed["perm"][b] for b in desc])
        valid_d = jnp.concatenate([packed["valid"][b] for b in desc])
        act_d = jnp.concatenate([packed["act"][b] for b in desc])
        mf = jnp.concatenate(
            [self._bucket_mask(packed, b, round_idx).reshape(-1)
             for b in desc]
        )
        xf, yf = packed["flat"]
        # static per-row storage geometry of the descending concat
        row_w = np.repeat(widths, rows).astype(np.int32)
        row_off = np.concatenate(
            [np.arange(r, dtype=np.int64) * w for w, r in zip(widths, rows)]
        )
        row_off += np.repeat(
            np.cumsum([0] + [w * r for w, r in zip(widths, rows)][:-1]),
            rows,
        )
        row_off = row_off.astype(np.int32)

        sel_rows = sel_loc[perm_d] & valid_d
        order = jnp.argsort(jnp.where(sel_rows, 0, 1))
        blocks, off = [], 0
        plan = self._packed_cohort_plan(widths, rows)
        for b, take in plan:
            wb = widths[b]
            idx = order[off : off + take]
            off += take
            pos = jnp.arange(wb, dtype=jnp.int32)
            gidx = jnp.asarray(row_off)[idx][:, None] + pos[None, :]
            m_g = mf[gidx] & (pos[None, :] < jnp.asarray(row_w)[idx][:, None])
            fields = dict(
                zip(self.model.data_keys, (xf[gidx], yf[gidx], act_d[idx]))
            )
            blocks.append((fields, m_g))
        locals_c = self._ragged_block_sgd(g_flat, blocks)
        slots = order[:off]
        cohort = (perm_d[slots], sel_rows[slots])
        return locals_c, cohort

    # ------------------------------------------------------------------
    def _round_step(self, state: EngineState, data, eval_set,
                    force_straggler, train_flops):
        """One communication round, fully traceable.  ``data``: dict with
        the model family's stacked per-client sample arrays (keys =
        ``self.model.data_keys``, client axis leading — e.g. x (N, n, 784) /
        y (N, n) / activations (N,) for the MNIST MLP, tokens (N, n, S) /
        labels (N, n, S) for LM clients), ``sizes`` (N,), plus the optional
        ragged-shard keys from ``data/datasets``: ``mask`` (N, n) bool marks
        the real (non-padding) samples, and ``round_mask`` (W, N, n) bool is
        a drift schedule — round t trains on window ``t mod W`` (``sizes``
        stays the static n_u aggregation weight).  Alternatively
        ``data["packed"]`` holds the bucketed packed layout (see
        ``_client_update``).  ``train_flops`` is the static per-client FLOP
        count of the virtual-latency model — computed host-side from the
        *dense* sample width so the physical layout (packed or padded)
        cannot shift straggler numerics.

        Each phase of the round runs under its ``tracing.phase`` scope
        (``faults`` ... ``eval``), which names its ops in a profile.

        Under mesh comms this body executes per-shard: the sample arrays
        (or the packed buckets), ``state.fg_history`` and
        ``state.pending_delta`` hold this shard's client block; everything
        (N,)-shaped is replicated, and cross-shard reductions go through
        ``self.comms``."""
        fed, comms = self.fed, self.comms
        key = jax.random.fold_in(jax.random.PRNGKey(fed.seed), state.round_idx)
        k_sel, k_lat, _k_poi = jax.random.split(key, 3)

        # --- fault injection (core/faults.py): this round's realization,
        # keyed on (seed, round, canonical client id) via a domain-
        # separated fold of the round key — the pinned 3-way split above
        # never moves, and faults="none" draws nothing at all
        fdraw = None
        if self.faults.active:
            with phase("faults"):
                fdraw = self.faults.draw(
                    key, jnp.arange(fed.num_clients, dtype=jnp.int32),
                    state.round_idx,
                )

        # --- Algorithm 2 lines 6-10: CheckResource + trust sort + sample
        # (global (N,) math, replicated across shards).  In cohort mode
        # (FedConfig.cohort_size) selection already ran HOST-side over the
        # client store (selection.sample_cohort) and every gathered row IS
        # a participant — ``cohort_valid`` marks the genuinely selected
        # slots (underfill slots are inert: all-False mask, zero weight).
        with phase("select"):
            if "cohort_valid" in data:
                selected = ok = data["cohort_valid"]
                if fdraw is not None:
                    # flapping / battery-dead clients fail CheckResource
                    # even though the host sampled them before the draw
                    selected = ok = selected & ~fdraw.unavailable
            else:
                res_sel = state.resources
                if fdraw is not None:
                    # an offline window reads as a dead battery to
                    # CheckResource; the persistent battery column is
                    # untouched
                    res_sel = res_sel._replace(
                        battery=jnp.where(fdraw.unavailable, 0.0,
                                          res_sel.battery)
                    )
                selected, ok = select_clients(
                    k_sel, state.trust, res_sel, self.req, fed
                )

        g_flat = state.params
        with phase("local_sgd"):
            # --- lines 16-21: ClientUpdate over this shard's clients (or
            # their gated cohort, ``locals_c`` over ``cohort``); non-
            # participants are masked out of the aggregate
            locals_flat, locals_c, cohort = self._client_update(
                g_flat, data, selected, state.round_idx
            )
            deltas = locals_flat - g_flat[None, :]  # (N_loc, D)
            # compact deltas: deviation + the fedar/fedavg reduction only
            # touch cohort rows (the rest are exact zeros), so with the
            # defense off XLA drops the canonical expansion from the gated
            # hot path
            delta_c = (None if locals_c is None
                       else locals_c - g_flat[None, :])
        crashed = None
        if fdraw is not None:
            # mid-round crash: the client trained (battery burns below) but
            # its uplink never reaches the server this round
            with phase("faults"):
                crashed = selected & fdraw.crash
            # corruption and quarantine rewrite canonical rows, so the
            # compact gated shortcut is invalid under an active schedule
            delta_c = cohort = None

        # --- virtual time: latency per client, straggler = late vs timeout
        with phase("latency"):
            model_bytes = self.dim * 4.0
            lat = round_latency(
                state.resources,
                train_flops=train_flops,
                model_bytes=model_bytes,
                key=k_lat,
            )
            if force_straggler is not None:
                lat = jnp.where(jnp.asarray(force_straggler),
                                fed.timeout * 3.0, lat)
            on_time = lat <= fed.timeout
            if crashed is not None:
                # crash-aware straggler masking: a crashed client reads as
                # a missed deadline (trust failure band), never as an
                # arrival
                on_time = on_time & ~crashed
            # the rows the server can ever receive this round (== selected
            # on the fault-free path, so every mask below is bit-identical
            # there)
            uplinked = selected if crashed is None else selected & ~crashed
            # rows actually visible server-side per mode: fedavg waits for
            # stragglers and async buffers them; fedar/async_seq skip on
            # timeout
            if fed.aggregation in ("fedavg", "async"):
                seen = uplinked
            else:
                seen = uplinked & on_time

        # --- uplink compression (core/compress.py): transmitting clients
        # send the encoded payload; the server decodes it and everything
        # downstream (deviation screen, defense history, aggregation)
        # consumes the DECODED rows.  Non-transmitting clients contribute
        # exact zeros and keep their error-feedback residual untouched.
        residual = state.compress_residual
        deltas_raw = transmit_g = None
        codec_rows_sent = jnp.zeros((), jnp.int32)
        if self.compression.active:
            with phase("codec.encode"):
                # per-mode transmit window: fedavg waits for stragglers, so
                # they transmit too; fedar's timeout-skipped clients never
                # upload; async transmits exactly when the buffer has a
                # slot to admit into (a free slot or an on-time supersede —
                # the client-side-knowable superset of _buffered_async's
                # admit gate, so error feedback is consumed iff the row can
                # land)
                if fed.aggregation == "fedavg":
                    transmit_g = uplinked
                elif fed.aggregation == "async":
                    lag0 = (jnp.floor(lat / fed.timeout).astype(jnp.int32)
                            == 0)
                    transmit_g = uplinked & (lag0 | ~state.pending_valid)
                else:
                    transmit_g = uplinked & on_time
                transmit = comms.local(transmit_g)
                codec_rows_sent = jnp.sum(transmit_g, dtype=jnp.int32)
                # stochastic codes keyed on the CANONICAL client id so
                # 1-device and sharded runs quantize bit-identically (the
                # round key's 3-way split above stays untouched for golden
                # stability)
                keys = compress_keys(
                    jax.random.fold_in(key, _COMPRESS_KEY_FOLD),
                    comms.local(jnp.arange(fed.num_clients,
                                           dtype=jnp.int32)),
                )
            # the gated compact view is a compute shortcut; post-decode the
            # canonical rows are what every downstream op must see
            delta_c = cohort = None
            deltas_raw = deltas
            # roundtrip opens the codec.encode / codec.decode scopes itself
            deltas, residual, payload = self.compression.roundtrip(
                deltas, residual, transmit, keys
            )
            comms.record_uplink(payload)

        # --- corrupt-uplink injection: garbage replaces the row the server
        # RECEIVES (post-decode, pre-quarantine) — exactly what a flipped
        # bit or truncated payload on the wire would produce
        if fdraw is not None:
            with phase("faults"):
                corrupt_g = fdraw.corrupt & (
                    transmit_g if transmit_g is not None else seen
                )
                c_loc = comms.local(corrupt_g)[:, None]
                deltas = jnp.where(c_loc, comms.local(fdraw.fill)[:, None],
                                   deltas)

        # --- non-finite quarantine at the decode boundary (ALWAYS on): a
        # NaN/Inf — or, past the configured magnitude cap, any garbage —
        # row contributes exact zeros instead of riding the scan carry
        # into the global model.  With finite rows every where() below is
        # an identity, so the fault-free path stays bit-identical.
        # one fused (N_loc, D) pass: the magnitude test rides the same
        # reduction as the finiteness test (a second max-abs reduction cost
        # ~13% of the round at N=128 — the fault win condition's budget)
        with phase("quarantine"):
            row_ok = jnp.isfinite(deltas)
            cap = fed.resolved_quarantine_cap
            if cap is not None:
                row_ok = row_ok & (jnp.abs(deltas) <= cap)
            q_loc = ~jnp.all(row_ok, axis=-1)
            deltas = jnp.where(q_loc[:, None], 0.0, deltas)
            if cohort is not None:
                delta_c = jnp.where(q_loc[cohort[0]][:, None], 0.0, delta_c)
            if self.compression.active:
                # dropped-uplink retry: a quarantined transmission consumed
                # its error-feedback residual for nothing — put the FULL raw
                # value (delta + pre-round residual) back in the residual so
                # the next transmission carries it (error feedback's
                # telescoping invariant extends to faults).  A non-finite raw value is
                # unrecoverable; fall back to the pre-round residual so the
                # carry is never poisoned.
                v = deltas_raw + state.compress_residual
                v_el = jnp.isfinite(v)
                if cap is not None:
                    v_el = v_el & (jnp.abs(v) <= cap)
                v_ok = jnp.all(v_el, axis=-1)
                retry = q_loc & comms.local(transmit_g)
                residual = jnp.where(
                    retry[:, None],
                    jnp.where(v_ok[:, None], v, state.compress_residual),
                    residual,
                )
            quarantined = comms.all_gather(q_loc)  # (N,) replicated

        # --- line 11: deviation ban + robust-defense weights
        with phase("deviation"):
            if fed.aggregation == "async":
                # no-wait: every (non-crashed) participant's update
                # eventually lands, so screen all of them
                active = uplinked
            else:
                active = selected & on_time
            # quarantined rows are zeroed — keep them out of the deviation
            # statistics (a zero row would drag the population mean) and
            # brand them deviated instead: exact-zero aggregation weight
            # plus the trust ban, the same fate as a caught poisoner
            screen = active & ~quarantined
            if cohort is None:
                deviated = agg.deviation_mask(
                    deltas, screen, fed.deviation_gamma, comms=comms
                )
            else:
                deviated = agg.deviation_mask(
                    delta_c, screen, fed.deviation_gamma, comms=comms,
                    cohort=cohort,
                )
            deviated = deviated | (seen & quarantined)
            contributing = active & ~deviated
        weights = data["sizes"].astype(jnp.float32)
        # pluggable defense (core/defense.py): the strategy owns its carried
        # history block (dense, sketched, or empty) and its weight statistic
        with phase("defense"):
            fg_history = self.defense.update_history(
                state.fg_history, deltas, contributing, comms=comms
            )
            fgw = self.defense.weights(fg_history, contributing, comms=comms)
            if fgw is not None:
                weights = weights * fgw

        # --- lines 13-14: aggregate
        pending = dict(
            delta=state.pending_delta,
            weight=state.pending_weight,
            issued=state.pending_issued,
            arrival=state.pending_arrival,
            valid=state.pending_valid,
        )
        agg_rows = deltas if cohort is None else delta_c
        with phase("aggregate"):
            if fed.aggregation == "fedavg":
                # synchronous: waits for everyone whose upload can still
                # land (stragglers included; crashed clients never arrive)
                sync_active = uplinked & ~deviated
                g_new = agg.fedavg_aggregate(
                    g_flat, agg_rows, weights, sync_active,
                    impl=fed.agg_impl, comms=comms, cohort=cohort,
                )
                round_time = jnp.max(jnp.where(uplinked, lat, 0.0))
            elif fed.aggregation == "async":
                g_new, pending = self._buffered_async(
                    g_flat, deltas, weights, contributing, lat, pending,
                    state.round_idx,
                )
                round_time = jnp.full((), fed.timeout)
            elif fed.aggregation == "async_seq":
                order = jnp.argsort(jnp.where(contributing, lat, jnp.inf))
                g_new = agg.async_aggregate(
                    g_flat, locals_flat, weights, contributing, order, fed,
                    comms=comms,
                )
                round_time = jnp.full((), fed.timeout)
            else:  # fedar (timeout skip)
                g_new = agg.fedavg_aggregate(
                    g_flat, agg_rows, weights, contributing,
                    impl=fed.agg_impl, comms=comms, cohort=cohort,
                )
                round_time = jnp.full((), fed.timeout)

        # --- line 15 + Algorithm 1: trust and battery evolution
        with phase("trust"):
            trust = update_trust(
                state.trust,
                fed,
                selected=selected,
                on_time=on_time,
                deviated=deviated,
                interested=ok,
            )
            resources = drain_battery(state.resources, selected)

        if eval_set is not None:
            with phase("eval"):
                params_tree = unflatten(g_new, self.template)
                loss, acc = self.model.metrics(params_tree, eval_set)
        else:
            loss = acc = jnp.full((), jnp.nan)

        new_state = EngineState(
            params=g_new,
            trust=trust,
            resources=resources,
            fg_history=fg_history,
            pending_delta=pending["delta"],
            pending_weight=pending["weight"],
            pending_issued=pending["issued"],
            pending_arrival=pending["arrival"],
            pending_valid=pending["valid"],
            compress_residual=residual,
            round_idx=state.round_idx + 1,
        )
        outputs = RoundOutputs(
            trust=trust.score,
            selected=selected,
            on_time=on_time,
            round_time=round_time,
            loss=loss,
            acc=acc,
            codec_rows_sent=codec_rows_sent,
        )
        return new_state, outputs

    # ------------------------------------------------------------------
    def _buffered_async(
        self, g_flat, deltas, weights, contributing, lat, pending, round_idx
    ):
        """FedBuff-style no-wait merge with a fixed-size buffer (one slot per
        client).  Fresh updates admitted this round land immediately when the
        client beat the timeout; straggler updates sit in the buffer and merge
        ``floor(lat / t)`` rounds later (an upload landing within a later
        round's timeout window joins that round's aggregation) with a
        ``(1 + tau)^-0.5`` staleness discount.  One masked weighted reduction
        per round — no O(N) sequential fold, so this is the mode that scales
        to 512-4096 clients.

        Slot bookkeeping (admit/issued/arrival/valid) is (N,) and replicated;
        only the delta buffer itself is a sharded (N_loc, D) block."""
        fed, comms = self.fed, self.comms
        # rounds until the update reaches the server (0 = within timeout)
        lag = jnp.floor(lat / fed.timeout).astype(jnp.int32)
        # admit into a free slot, or supersede an in-flight STALE update with
        # a fresh on-time one; a straggler that keeps getting selected must
        # not clobber its own still-in-transit upload every round, or the
        # buffered update would never arrive
        admit = contributing & ((lag == 0) | ~pending["valid"])
        delta_buf = jnp.where(comms.local(admit)[:, None], deltas,
                              pending["delta"])
        weight_buf = jnp.where(admit, weights, pending["weight"])
        issued = jnp.where(admit, round_idx, pending["issued"])
        arrival = jnp.where(admit, round_idx + lag, pending["arrival"])
        valid = admit | pending["valid"]

        delivered = valid & (arrival <= round_idx)
        staleness = jnp.maximum(round_idx - issued, 0).astype(jnp.float32)
        if fed.staleness_decay == "const":
            staleness_arg = None
        else:
            staleness_arg = staleness
        g_new = agg.fedavg_aggregate(
            g_flat,
            delta_buf,
            weight_buf,
            delivered,
            staleness=staleness_arg,
            impl=fed.agg_impl,
            comms=comms,
        )
        return g_new, dict(
            delta=delta_buf,
            weight=weight_buf,
            issued=issued,
            arrival=arrival,
            valid=valid & ~delivered,
        )

    # ------------------------------------------------------------------
    def _shard(self, fn, state, data, eval_set, force_straggler):
        """Run ``fn(state, data, eval_set, force_straggler)`` per client
        shard (or as-is on one device).  Both entry points share this so the
        spec plumbing cannot diverge between ``step`` and ``run``."""
        if self.mesh is None:
            return fn(state, data, eval_set, force_straggler)
        return jax.shard_map(
            fn,
            mesh=self.mesh,
            in_specs=self._in_specs(data, eval_set, force_straggler),
            out_specs=(self.state_specs(), self._round_out_specs()),
            check_vma=False,
        )(state, data, eval_set, force_straggler)

    def _step_fn(self, state, data, eval_set, force_straggler, *,
                 train_flops: float):
        def body(state, data, eval_set, force_straggler):
            return self._round_step(
                state, self._with_flat_packed(data), eval_set,
                force_straggler, train_flops,
            )

        return self._shard(body, state, data, eval_set, force_straggler)

    def _run_fn(self, state, data, eval_set, force_straggler, *, rounds: int,
                train_flops: float):
        def scan_rounds(state, data, eval_set, force_straggler):
            data_aug = self._with_flat_packed(data)

            def body(carry, _):
                return self._round_step(
                    carry, data_aug, eval_set, force_straggler, train_flops
                )

            return jax.lax.scan(body, state, None, length=rounds)

        return self._shard(scan_rounds, state, data, eval_set, force_straggler)

    # ------------------------------------------------------------------
    def _train_flops(self, data) -> float:
        """Static per-client FLOP count for the virtual-latency model,
        delegated to the model family; the sample-block shape comes from
        the DENSE width (``n_max`` for packed layouts) — the physical
        layout must not move straggler numerics."""
        if "packed" in data:
            n = int(np.asarray(data["packed"]["n_max"]))
            shape = (n,) + tuple(data["packed"]["x"][0].shape[2:])
        else:
            shape = tuple(data[self.model.data_keys[0]].shape[1:])
        return float(
            self.model.train_flops(shape, epochs=self.fed.local_epochs)
        )

    def _decide_sgd_route(self) -> str:
        """The local-SGD route: ``"fused_ragged"`` (the model's fused
        kernel, one ragged-grid launch over every client block, dense or
        packed) or ``"xla"`` (vmapped ``client_update``).  The kernel only
        applies to families that ship one — an explicit ``"kernel"``
        request on any other family runs the XLA path, loudly.  The kernel
        streams one batch tile per grid step, so what must fit its compiled
        VMEM limit is one ``local_batch_size`` tile whatever the layout or
        the clients' widths; a tile that does not fit runs XLA under
        ``sgd_impl="auto"`` and raises under an explicit ``"kernel"``."""
        fed, model = self.fed, self.model
        if resolve_impl(fed.sgd_impl, "sgd") != "kernel":
            return "xla"
        if not model.supports_fused:
            if fed.sgd_impl == "kernel":
                warnings.warn(
                    f'sgd_impl="kernel" requests the fused Pallas local-SGD '
                    f"kernel, but model family {model.family!r} does not "
                    f"ship one; falling back to the vmapped XLA path",
                    stacklevel=3,
                )
            return "xla"
        bs = fed.local_batch_size
        if model.fused_fits(bs):
            return "fused_ragged"
        if fed.sgd_impl == "kernel":
            raise ValueError(
                f'sgd_impl="kernel": a {bs}-sample batch tile does not fit '
                f"the fused local-SGD kernel's compiled VMEM limit; use "
                f'sgd_impl="auto" or "einsum", or a smaller '
                f"local_batch_size"
            )
        return "xla"

    def _check_packed(self, data) -> None:
        """Host-side layout check: a packed dict built for k shards only
        scatters correctly on a k-shard mesh (its ``perm`` is shard-local),
        and only ``packed_supported`` model families understand it."""
        if "packed" not in data:
            return
        if not self.model.packed_supported:
            raise ValueError(
                f"model family {self.model.family!r} does not support the "
                f"bucketed packed layout; pass the dense per-client arrays "
                f"(FederatedDataset.arrays()) instead"
            )
        built = int(np.asarray(data["packed"]["shards"]))
        if built != self.comms.shards:
            raise ValueError(
                f"packed data was built for {built} shard(s) "
                f"(FederatedDataset.packed_arrays(shards=...)) but the "
                f"engine runs {self.comms.shards}; rebuild the packed "
                f"layout for the active mesh"
            )

    def prepare_data(self, ds, layout: str = "auto"):
        """Build this engine's data dict from a ``FederatedDataset``,
        picking dense-vs-packed PER FLEET from the ``scenarios.
        padding_waste`` estimate (``pick_layout``) under this engine's
        mesh shard count and batch quantum — heavy quantity skew gets the
        padding-free bucketed layout, near-uniform fleets keep the cheaper
        single-rectangle vmap.  ``layout`` in {"auto", "dense", "packed"}
        overrides the pick.  The fleet must already be padded to the mesh
        (``FederatedDataset.padded_to``) so its client count matches
        ``FedConfig.num_clients``."""
        if ds.num_clients != self.fed.num_clients:
            raise ValueError(
                f"dataset has {ds.num_clients} clients but FedConfig.num_"
                f"clients={self.fed.num_clients}; pad the fleet first "
                f"(FederatedDataset.padded_to(shards)) and build the config "
                f"from the padded count"
            )
        raw = ds.engine_arrays(
            shards=self.comms.shards,
            quantum=self.fed.local_batch_size,
            layout=layout,
        )
        if self.mesh is None:
            return jax.tree.map(jnp.asarray, raw)
        # host -> each shard's device directly, never whole on one device
        return self._on_mesh(raw, self.data_specs(raw))

    def kernel_routes(self) -> dict:
        """The backend each hot op takes: ``sgd`` is ``sgd_route``; ``agg``,
        ``defense`` and ``compress`` are ``"kernel"`` (Pallas) or
        ``"einsum"`` (XLA), or ``"none"`` where the subsystem is off; a
        top-k ``compress`` names its selection's backend."""
        fed = self.fed
        return {
            "sgd": self.sgd_route,
            "agg": ("scan" if fed.aggregation == "async_seq"
                    else resolve_impl(fed.agg_impl, "agg")),
            "defense": ("none" if self.defense.name == "none"
                        else resolve_impl(fed.defense_impl, "defense")),
            "compress": self.compression.route(self.dim),
        }

    def lower_step(self, state, data, *, eval_set=None):
        """The lowered one-round program ``step`` would run on these
        inputs (``.as_text()`` shows which kernels it calls)."""
        self._check_packed(data)
        return self._step.lower(*self._placed(state, data, eval_set, None),
                                train_flops=self._train_flops(data))

    def step(self, state, data, *, eval_set=None, force_straggler=None):
        """One jitted communication round -> (state, RoundOutputs).  Host
        spans ``fedar.prepare`` and ``fedar.dispatch`` (stat ``compiles``:
        the compilations the call triggered); the call returns before the
        device finishes."""
        with span("fedar.prepare"):
            self._check_packed(data)
            args = self._placed(state, data, eval_set, force_straggler)
            train_flops = self._train_flops(data)
        with span("fedar.dispatch") as s:
            traced = tracing.enabled()
            if traced:
                before = tracing.compile_count()
            out = self._step(*args, train_flops=train_flops)
            if traced:
                s.set_metadata(compiles=tracing.compile_count() - before)
        return out

    def run(self, state, data, *, rounds: int, eval_set=None,
            force_straggler=None):
        """R rounds in a single ``lax.scan`` -> (state, stacked outputs)."""
        self._check_packed(data)
        return self._run(
            *self._placed(state, data, eval_set, force_straggler),
            rounds=rounds, train_flops=self._train_flops(data),
        )


class CohortEngine:
    """Host-store cohort driver: fleets bigger than one scan carry.

    The resident ``FedAREngine`` keeps all N clients' trust / battery /
    defense history / data resident on device, so N is an engine limit.
    This driver makes N a dataset property instead: the full fleet lives in
    a numpy ``ClientStore`` on the host, and each round

      1. ``selection.sample_cohort`` draws a static-shape cohort of
         K = ``FedConfig.cohort_size`` clients from the store (trust +
         CheckResource over the host columns, keyed ``(seed, round)``),
      2. the fleet object materializes ONLY those K clients' samples
         (``cohort_arrays``) and the store ``gather``\\ s their state rows,
      3. a sub-``FedAREngine`` built at ``num_clients=K`` runs the
         unchanged jitted round body (one compile for the whole run —
         cohort shapes are static and the input key set never changes),
      4. trust / battery / history rows ``scatter_round`` back and
         ``finish_round`` evolves the non-cohort population host-side.

    Per-round device memory is O(K*D + K*samples), independent of N; the
    host pays O(N * smallstate).  Inside the cohort the sub-engine selects
    participants exactly as the resident engine would have among those K
    (the ``cohort_valid`` mask pre-gates eligibility), and on a mesh the
    sub-engine aggregates with the two-level tree reduce
    (``MeshComms.reduce_tree``) so cross-shard traffic is O(D/k) per
    device.

    K >= N is NOT this class's job: ``FedARServer`` strips ``cohort_size``
    and runs the resident engine, which is bit-identical to the
    pre-cohort code path.
    """

    def __init__(
        self,
        model: Union[ClientModel, MnistConfig],
        fed: FedConfig,
        req: TaskRequirement,
        *,
        lr: float = 0.1,
    ):
        if fed.cohort_size is None:
            raise ValueError("CohortEngine needs FedConfig.cohort_size set")
        if fed.cohort_size >= fed.num_clients:
            raise ValueError(
                f"cohort_size={fed.cohort_size} >= num_clients="
                f"{fed.num_clients}: the whole fleet fits on device — use "
                f"the resident engine (FedARServer does this automatically)"
            )
        if fed.aggregation == "async_seq":
            raise ValueError(
                "aggregation='async_seq' folds every client's full local "
                "model sequentially per round (O(N) and no per-client "
                "buffer to persist), which a resampled cohort cannot "
                "replay; use aggregation='async' — its pending-delta "
                "buffer lives in the client store and follows the cohort"
            )
        if fed.select_frac is not None:
            raise ValueError(
                "select_frac gating composes with the resident engine "
                "only; the cohort IS the statically-capped set — drop "
                "select_frac and lower cohort_size instead"
            )
        self.fed, self.req, self.lr = fed, req, lr
        # the device-side engine is the UNCHANGED round body at fleet size
        # K: same selection, SGD, defense, trust and battery updates, with
        # the two-level tree reduce on a mesh.  Synthetic fleet knobs
        # (starved / poisoner counts) are host-store properties, not
        # sub-engine ones — the cohort's real resource rows and data
        # override the sub-engine's make_fleet output every round.
        sub = dataclasses.replace(
            fed,
            num_clients=fed.cohort_size,
            cohort_size=None,
            num_starved=0,
            num_poisoners=0,
            tree_reduce=True,
        )
        self.engine = FedAREngine(model, sub, req, lr=lr)
        if not self.engine.defense.cohort_compatible:
            raise ValueError(
                f"defense {self.engine.defense.name!r} is not cohort-"
                f"compatible: its per-client history is O(model_dim), so "
                f"the host store would be O(N*D); use 'foolsgold_sketch' "
                f"(O(N*r)) or 'none'"
            )
        self.model = self.engine.model
        self.template = self.engine.template
        self.dim = self.engine.dim
        self.mesh = self.engine.mesh
        self.compression = self.engine.compression
        self.codec_rows = self.engine.codec_rows
        self.faults = self.engine.faults
        self.store = ClientStore(
            fed,
            self.engine.defense.history_dim(self.dim),
            residual_dim=self.engine.compression.residual_dim(self.dim),
            # store-resident async: the (N, D) pending-delta buffer lives
            # in the host table and follows the cohort on/off device, so
            # an in-flight update survives its client leaving the device
            pending_dim=self.dim if fed.aggregation == "async" else 0,
        )
        self.poison_mask = self.store.poison_mask
        self.params = flatten(self.template)
        self._state0 = self.engine.init_state()

    # ------------------------------------------------------------------
    @property
    def round_idx(self) -> int:
        return int(self.store.round_idx)

    def _build_round_inputs(self, fleet):
        """Sample the round's cohort and assemble the device inputs: the
        jit-boundary pytree is shaped by K alone (the memory-independence
        contract — N never appears in a device shape)."""
        r = int(self.store.round_idx)
        with span("cohort.sample"):
            idx, valid, elig = sample_cohort(
                self.store.score,
                self.store.resources_view(),
                self.req,
                self.fed,
                cohort_size=self.fed.cohort_size,
                round_idx=r,
            )
        with span("cohort.arrays"):
            arrays = fleet.cohort_arrays(idx, valid)
        with span("cohort.gather"):
            rows = self.store.gather(idx)
        with span("cohort.h2d"):
            data = jax.tree.map(jnp.asarray, arrays)
            state = self._state0._replace(
                params=jnp.asarray(self.params),
                trust=TrustState(
                    jnp.asarray(rows["score"]),
                    jnp.asarray(rows["participations"]),
                    jnp.asarray(rows["failures"]),
                ),
                resources=ResourceState(
                    jnp.asarray(rows["memory"]),
                    jnp.asarray(rows["bandwidth"]),
                    jnp.asarray(rows["battery"]),
                    jnp.asarray(rows["compute"]),
                ),
                fg_history=jnp.asarray(rows["history"]),
                compress_residual=jnp.asarray(rows["residual"]),
                round_idx=jnp.asarray(r, jnp.int32),
            )
            if self.store.pending_dim:
                # the cohort's in-flight async slots ride along; issue/
                # arrival tags are absolute rounds, so an update whose
                # client sat out a few rounds delivers (staleness-
                # discounted) when it rejoins
                state = state._replace(
                    pending_delta=jnp.asarray(rows["pending_delta"]),
                    pending_weight=jnp.asarray(rows["pending_weight"]),
                    pending_issued=jnp.asarray(rows["pending_issued"]),
                    pending_arrival=jnp.asarray(rows["pending_arrival"]),
                    pending_valid=jnp.asarray(rows["pending_valid"]),
                )
        return state, data, idx, valid, elig

    def kernel_routes(self) -> dict:
        return self.engine.kernel_routes()

    def lower_round(self, fleet, *, eval_set=None):
        """The lowered program of the next round (its cohort sampled, the
        store untouched)."""
        state, data, *_ = self._build_round_inputs(fleet)
        return self.engine.lower_step(state, data, eval_set=eval_set)

    def run_round(self, fleet, *, eval_set=None):
        """One store-sampled round -> (idx, valid, RoundOutputs).

        ``idx``/``valid`` name the (K,) cohort; the outputs' client axis is
        cohort-indexed (row j belongs to fleet client ``idx[j]`` where
        ``valid[j]``)."""
        state, data, idx, valid, elig = self._build_round_inputs(fleet)
        with span("cohort.step"):
            state2, out = self.engine.step(state, data, eval_set=eval_set)
        self.params = state2.params
        with span("fedar.wait"):
            jax.block_until_ready((state2, out))
        with span("cohort.scatter") as s:
            back = dict(
                trust=TrustState(
                    np.asarray(state2.trust.score),
                    np.asarray(state2.trust.participations),
                    np.asarray(state2.trust.failures),
                ),
                battery=np.asarray(state2.resources.battery),
                history=np.asarray(state2.fg_history),
                residual=np.asarray(state2.compress_residual),
                pending=None if not self.store.pending_dim else dict(
                    pending_delta=np.asarray(state2.pending_delta),
                    pending_weight=np.asarray(state2.pending_weight),
                    pending_issued=np.asarray(state2.pending_issued),
                    pending_arrival=np.asarray(state2.pending_arrival),
                    pending_valid=np.asarray(state2.pending_valid),
                ),
            )
            if tracing.enabled():
                s.set_metadata(bytes=sum(
                    a.nbytes for a in jax.tree.leaves(back)))
            self.store.scatter_round(idx, valid, **back)
        with span("cohort.finish"):
            self.store.finish_round(idx, valid, elig)
        return idx, valid, out

    def run(self, fleet, *, rounds: int, eval_set=None):
        """R store-sampled rounds; returns a list of per-round
        ``(idx, valid, RoundOutputs-as-numpy)`` tuples."""
        if fleet.num_clients != self.fed.num_clients:
            raise ValueError(
                f"fleet has {fleet.num_clients} clients but FedConfig."
                f"num_clients={self.fed.num_clients}"
            )
        outs = []
        for _ in range(rounds):
            idx, valid, out = self.run_round(fleet, eval_set=eval_set)
            outs.append((idx, valid, jax.tree.map(np.asarray, out)))
        return outs
