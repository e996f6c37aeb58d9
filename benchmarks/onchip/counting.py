"""Counts the benchmark's metrics are built on, worked out from shapes.

Kept with the benchmark so that no change to the program can move them:
the real work of a round, the operations and bytes the local-SGD
algorithm needs for it, and the published peaks of each chip.  A
configuration gives its readers its own counts (``Counts``); the MLP's are
here.
"""
from __future__ import annotations

from typing import Callable, NamedTuple, Optional

import numpy as np


class ChipPeaks(NamedTuple):
    """Published per-chip peaks of one accelerator kind."""

    flops_bf16: float  # FLOP/s
    hbm_bw: float  # B/s
    source: str


# Keyed by ``jax.Device.device_kind``; a kind missing here is an error,
# never a default.  Copied from the program's ``launch/mesh.PEAKS``.
PEAKS = {
    "TPU v5 lite": ChipPeaks(
        flops_bf16=197e12,
        hbm_bw=819e9,
        source='Google Cloud documentation, "TPU v5e"',
    ),
}


class Counts(NamedTuple):
    """A configuration's operation and byte counts, as its
    ``counts(spec)`` gives them to the per-layer readers."""

    # one sample (for a language model, one sequence) through one epoch
    # of local SGD
    flops_per_sample_epoch: float
    # ``(clients, sample_epochs) -> (flops, bytes)`` of the fused
    # local-SGD kernel, for a configuration that runs it
    local_sgd_work: Optional[Callable[[int, int], tuple]] = None


def chip_peaks(device_kind: str) -> ChipPeaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r} "
            f"(known: {sorted(PEAKS)})"
        ) from None


def sample_epochs(selected, sizes, epochs: int) -> int:
    """Real samples that went through local SGD in one round, times the
    local epochs: the selected clients' true sizes.  Padding rows and
    unselected clients add nothing."""
    selected = np.asarray(selected, bool)
    return int(np.asarray(sizes, np.int64)[selected].sum()) * int(epochs)


def flops_per_sample_epoch(model: dict) -> int:
    """Operations one sample needs in one SGD pass of the MLP
    ``d_in -> hidden -> classes``: the forward matmuls (2 per
    multiply-add), the weight gradients of both layers and the hidden
    gradient.  The input gradient of the first layer is not needed and not
    counted; elementwise work is left out."""
    d, h, c = model["input_dim"], model["hidden"], model["num_classes"]
    forward = 2 * (d * h + h * c)
    backward = 2 * d * h + 2 * h * c + 2 * h * c  # dW1, dW2, dh
    return forward + backward


def param_count(model: dict) -> int:
    d, h, c = model["input_dim"], model["hidden"], model["num_classes"]
    return d * h + h + h * c + c


def local_sgd_work(clients: int, epochs_samples: int, model: dict):
    """``(flops, bytes)`` the local-SGD algorithm needs for ``clients``
    clients and ``epochs_samples`` real sample-epochs among them: every
    sample-epoch's operations, every sample read once per epoch (float32
    features and an int32 label), and each client's float32 parameters read
    and written once."""
    flops = flops_per_sample_epoch(model) * epochs_samples
    sample_bytes = 4 * (model["input_dim"] + 1)
    nbytes = sample_bytes * epochs_samples + 2 * 4 * param_count(model) * clients
    return float(flops), float(nbytes)


def roofline_seconds(flops: float, nbytes: float, peaks: ChipPeaks):
    """The least time the chip could take, and which peak bounds it."""
    t_flops, t_bytes = flops / peaks.flops_bf16, nbytes / peaks.hbm_bw
    return max(t_flops, t_bytes), ("compute" if t_flops >= t_bytes
                                   else "memory")
