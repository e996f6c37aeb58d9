"""The ``ClientModel`` protocol — the surface ``FedAREngine`` trains against.

The engine is model-agnostic: it carries the global model as one flat
``(D,)`` float32 vector (the *aggregation boundary* — ``fedavg_agg``, the
deviation ban and the count-sketch defense all operate on flat deltas) and
delegates everything model-shaped to a ``ClientModel``:

  ``init(key)``           -- build one client's param pytree (any nesting,
                             any leaf dtypes; ``core.engine.flatten`` /
                             ``unflatten`` adapt it to the flat boundary).
  ``loss(params, fields)``-- scalar training loss on one client's samples.
  ``client_update``       -- Algorithm 2's ClientUpdate: E epochs of local
                             minibatch SGD for ONE client (the engine vmaps
                             it over the client block).
  ``metrics``             -- (eval_loss, eval_accuracy) on a held-out set.
  ``train_flops``         -- static per-client FLOP count feeding the
                             virtual-latency straggler model.

``fields`` is a dict of ONE client's sample arrays, keyed by ``data_keys``
(the engine slices them out of the stacked per-client data dict, so a data
builder and a model agree through these names alone).  ``sample_mask`` is
the engine-resolved ragged/drift mask over the sample axis, or ``None`` on
the dense path.

Capability flags gate the engine's specialized hot paths:

  ``supports_fused``   -- model ships a fused Pallas local-SGD kernel;
                          ``fused_block_update`` may take a whole client
                          block in one ``pallas_call`` when ``fused_fits``
                          admits its width.  When False, the engine runs
                          the vmapped XLA path (and warns if
                          ``sgd_impl="kernel"`` was forced).
  ``packed_supported`` -- model understands the size-bucketed packed layout
                          (``FederatedDataset.packed_arrays``); the packed
                          buckets reuse ``data_keys`` field names.
"""
from __future__ import annotations


class ClientModel:
    """Base class / protocol for engine-trainable client model families.

    Subclasses must override ``init``, ``loss``, ``client_update``,
    ``metrics`` and ``train_flops``; the hot-path hooks below have safe
    defaults (no fused kernel, no packed layout).
    """

    family: str = "client"
    #: keys of the stacked per-client arrays this model trains on, in the
    #: order the data builder stacks them; each is (N, ...) client-major
    data_keys: tuple = ()
    supports_fused: bool = False
    packed_supported: bool = False

    # ------------------------------------------------------------- core
    def init(self, key):
        """One client's parameter pytree."""
        raise NotImplementedError

    def loss(self, params, fields, sample_mask=None):
        """Scalar training loss over one client's ``fields``."""
        raise NotImplementedError

    def client_update(self, params, fields, *, lr, batch_size, epochs,
                      sample_mask=None):
        """E epochs of local minibatch SGD for one client -> new params."""
        raise NotImplementedError

    def metrics(self, params, eval_set):
        """(loss, accuracy) on the held-out ``eval_set``."""
        raise NotImplementedError

    def train_flops(self, sample_shape, *, epochs) -> float:
        """Static per-client FLOPs for the virtual-latency model.
        ``sample_shape`` is one client's dense sample-block shape (sample
        axis first), taken from ``data_keys[0]``."""
        raise NotImplementedError

    # ------------------------------------------------- hot-path hooks
    def fused_fits(self, width: int, batch_size: int) -> bool:
        """Whether the fused kernel can take a client block whose sample
        axis is ``width`` wide (``supports_fused`` families override)."""
        return False

    def fused_block_update(self, global_flat, fields, sample_mask, *,
                           lr, batch_size, epochs):
        """Fused-kernel ClientUpdate over a whole client block: the stacked
        post-SGD flat params (rows, D), in the same leaf order as
        ``core.engine.flatten``.  The engine calls it only on the route it
        decided from ``fused_fits``."""
        raise NotImplementedError
