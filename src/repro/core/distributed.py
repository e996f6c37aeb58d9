"""Mesh layer of the unified FedAR engine (client-sharded collectives).

The standalone mesh step builder this module used to be is absorbed into
``core/engine.py``: there is ONE engine, and this module supplies the pieces
that make its ``lax.scan`` round loop run sharded over a ``clients`` mesh
axis.  ``FedAREngine`` wraps its scan body in a ``shard_map`` when
``FedConfig.mesh_shape > 1``; every client-indexed ``(N, ...)`` tensor —
stacked local datasets, FoolsGold history, the buffered-async delta buffer —
splits into ``N / mesh_shape`` blocks, while the ``(N,)`` bookkeeping
vectors (trust, resources, masks) replicate so selection's global sort and
Algorithm 1's trust updates stay bit-identical to the single-device engine.

Exports:

  ``client_mesh``   -- build the 1-D ``clients`` mesh from ``FedConfig``
                       (``None`` on the single-device path; too few
                       devices is an error).
  ``ClientComms``   -- identity collectives: the single-device engine and
                       the comms-parameterized math in ``core/aggregation``
                       / ``core/foolsgold`` reduce to the seed numerics.
  ``MeshComms``     -- the same interface over ``jax.lax`` collectives
                       inside ``shard_map``: aggregation becomes a
                       trust*staleness-weighted ``psum`` that GSPMD
                       schedules like a data-parallel reduction, and the
                       defense's pairwise similarity becomes a gathered
                       block product (see ``core/foolsgold.py``).  The
                       ``gather_defense`` collective carries the defense
                       history payload — (N, r) sketches for
                       ``foolsgold_sketch`` instead of the dense (N, D)
                       history — and records gathered shapes so tests can
                       assert the payload stays sketched.
  ``client_spec`` / ``replicated_spec`` -- the ``PartitionSpec`` vocabulary
                       the engine threads through its in/out specs.

(The old parallel LM cohort step — ``build_fedar_train_step`` /
``build_fedar_local_rounds`` — is gone: transformer clients now run through
``FedAREngine`` behind the ``ClientModel`` protocol, see
``models/client.py`` and ``examples/federated_lm.py``.  Plain data-parallel
LM pre-training lives in ``launch/train.py``.)
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from repro.common.config import FedConfig


# ---------------------------------------------------------------------------
# Client-mesh collectives for the unified engine
# ---------------------------------------------------------------------------

class ClientComms:
    """Collective vocabulary of the engine's round math, identity flavour.

    The round step is written once against this interface; on a single
    device every method is the identity so the math is exactly the seed
    engine's.  ``MeshComms`` swaps in the real collectives inside
    ``shard_map``.  Convention: "local" arrays hold this shard's block of
    clients along axis 0; "global" arrays hold all N clients (replicated).
    """

    axis: Optional[str] = None
    shards: int = 1

    def __init__(self):
        # gathered defense payload shapes, recorded at trace time — the
        # mesh tests assert the sketch defense ships (N, r) not (N, D)
        self.defense_gather_shapes: list = []
        # per-leaf (shape, dtype.name) of each round's compressed uplink
        # payload (``core/compress.py``), also trace-time — the mesh /
        # bench tests assert the wire format stays packed (uint8 codes /
        # (k,) pairs), not silently re-densified fp32
        self.uplink_payload_shapes: list = []

    def record_uplink(self, payload) -> None:
        """Record a compression payload pytree's leaf shapes/dtypes (the
        per-shard uplink that crosses the client->aggregator boundary),
        from arrays or ``jax.ShapeDtypeStruct`` leaves alike."""
        self.uplink_payload_shapes.append(tuple(
            (tuple(leaf.shape), leaf.dtype.name)
            for leaf in jax.tree.leaves(payload)
        ))

    def psum(self, x):
        """Sum a shard-local partial across the client axis."""
        return x

    def all_gather(self, x):
        """Concatenate shard-local rows into the full (N, ...) array."""
        return x

    def local(self, x):
        """Slice this shard's client block out of a replicated (N, ...)."""
        return x

    def gather_defense(self, x):
        """All-gather a defense history payload (the sketched (N_loc, r)
        projection, or the dense (N_loc, D) block for the legacy strategy)
        across the client axis, recording the gathered shape.  This is the
        defense's one all-to-all — its payload, not the O(N*D) history,
        bounds the per-device defense footprint."""
        out = self.all_gather(x)
        self.defense_gather_shapes.append(tuple(out.shape))
        return out

    def reduce_tree(self, x):
        """Two-level cross-shard reduction of a (D,) partial: each shard
        already holds its leaf-psum'd block partial, and the cross-shard
        phase reduce-scatters a 1/k slice onto every device before
        all-gathering the reduced slices back (vs one flat ``psum`` that
        materializes the whole (D,) operand per device).  Identity on one
        device; ``MeshComms`` implements the tree when enabled."""
        return self.psum(x)


class MeshComms(ClientComms):
    """``jax.lax`` collectives over the ``clients`` mesh axis.

    ``tree=True`` (``FedConfig.tree_reduce``) routes ``reduce_tree``
    through the two-phase reduce-scatter + all-gather formulation —
    the hierarchical aggregation path the cohort engine enables; the
    default flat ``psum`` keeps the resident mesh's pinned reduction
    order."""

    def __init__(self, axis: str, shards: int, *, tree: bool = False):
        super().__init__()
        self.axis, self.shards = axis, shards
        self.tree = tree

    def psum(self, x):
        return jax.lax.psum(x, self.axis)

    def all_gather(self, x):
        return jax.lax.all_gather(x, self.axis, axis=0, tiled=True)

    def local(self, x):
        n_local = x.shape[0] // self.shards
        start = jax.lax.axis_index(self.axis) * n_local
        return jax.lax.dynamic_slice_in_dim(x, start, n_local, axis=0)

    def reduce_tree(self, x):
        """Cross-shard reduce of a (D,) per-shard partial.  Tree mode pads
        D to a shard multiple, reduce-scatters so each device sums only its
        D/k slice (grouped ``psum`` with ``axis_index_groups`` is
        unimplemented on CPU shard_map, so the scatter phase IS the leaf
        level of the tree), then all-gathers the reduced slices — each
        device touches O(D/k) during the reduction instead of the full
        (D,) operand a flat psum materializes."""
        if not self.tree or self.shards == 1 or x.ndim != 1:
            return self.psum(x)
        d = x.shape[0]
        pad = (-d) % self.shards
        padded = jnp.pad(x, (0, pad)) if pad else x
        leaf = jax.lax.psum_scatter(
            padded, self.axis, scatter_dimension=0, tiled=True
        )
        full = jax.lax.all_gather(leaf, self.axis, axis=0, tiled=True)
        return full[:d]


def client_mesh(fed: FedConfig) -> Optional[Mesh]:
    """The 1-D ``clients`` mesh ``FedConfig.mesh_shape`` asks for, or
    ``None`` for the single-device path (``mesh_shape`` unset / 1).  A host
    with fewer devices than requested is an error: a narrower mesh would
    attribute results to shards that do not exist.  ``num_clients`` must
    divide evenly into the shards so every block is rectangular."""
    shards = fed.mesh_shape or 1
    if shards <= 1:
        return None
    devices = jax.devices()
    if len(devices) < shards:
        raise ValueError(
            f"mesh_shape={shards} requested but only {len(devices)} "
            f"device(s) are available"
        )
    if fed.num_clients % shards:
        raise ValueError(
            f"num_clients={fed.num_clients} not divisible by {shards} "
            f"client shards (mesh_shape={shards})"
        )
    return Mesh(np.array(devices[:shards]), (fed.client_axis,))


def client_spec(fed: FedConfig) -> P:
    """PartitionSpec for client-indexed (N, ...) tensors: shard axis 0."""
    return P(fed.client_axis)


def window_client_spec(fed: FedConfig) -> P:
    """PartitionSpec for round-windowed client tensors (W, N, ...) — the
    drift schedule's ``round_mask`` — sharding the client axis (axis 1)."""
    return P(None, fed.client_axis)


def replicated_spec() -> P:
    """PartitionSpec for replicated state (params, (N,) bookkeeping)."""
    return P()


def packed_specs(fed: FedConfig, packed: dict) -> dict:
    """PartitionSpecs for a bucketed packed-data dict
    (``FederatedDataset.packed_arrays``): every per-bucket row-indexed array
    shards its row axis over the ``clients`` mesh (buckets are laid out
    shard-major with equal per-shard row counts, so a plain row split lands
    each shard exactly its clients), ``round_mask`` buckets shard axis 1
    like the dense drift schedule, and the scalar metadata replicates."""
    Pc, Pr = client_spec(fed), replicated_spec()
    specs = {
        key: tuple(Pc for _ in packed[key])
        for key in ("x", "y", "mask", "perm", "valid", "act")
    }
    specs["inv"] = Pc  # (N,) canonical -> shard-local packed row
    specs["n_max"] = Pr
    specs["shards"] = Pr
    if "round_mask" in packed:
        specs["round_mask"] = tuple(
            window_client_spec(fed) for _ in packed["round_mask"]
        )
    return specs

