"""Public jit'd wrappers for the Pallas kernels, and the one place that
decides how a kernel runs.

Each op dispatches between the Pallas kernel and the pure-XLA reference
path.  ``interpret_mode`` is the single call-time switch between compiling
a kernel for the chip (TPU backend) and emulating it with
``interpret=True`` (any other backend); nothing here touches the backend
while the module is imported.
"""
from __future__ import annotations

import functools

import jax

from repro.kernels import ref
from repro.kernels.compress import pack_codes as _pack_codes_kernel
from repro.kernels.compress import unpack_codes as _unpack_codes_kernel
from repro.kernels.fedavg_agg import fedavg_agg as _fedavg_agg_kernel
from repro.kernels.flash_attention import flash_attention as _flash_kernel
from repro.kernels.local_sgd import local_sgd_fused as _local_sgd_kernel
from repro.kernels.ssm_scan import ssm_scan as _ssm_kernel
from repro.kernels.topk_select import topk_select as _topk_select_kernel

_IMPL_KINDS = ("sgd", "agg", "defense", "compress")
_IMPL_VALUES = ("auto", "kernel", "einsum")


def interpret_mode() -> bool:
    """Whether Pallas kernels run under the interpreter: True unless the
    default backend is a TPU, where they compile for the chip.  Asked at
    call (trace) time, never at import."""
    return jax.default_backend() != "tpu"


def resolve_impl(name: str, kind: str) -> str:
    """Resolve one of the engine's kernel-routing knobs (``FedConfig.sgd_impl``
    / ``agg_impl`` / ``defense_impl`` / ``compress_impl``) to a concrete
    backend.

    All the knobs share the same vocabulary: ``"auto"`` picks the Pallas
    kernel on a TPU backend and the XLA einsum path elsewhere; ``"kernel"`` /
    ``"einsum"`` force the choice (off-TPU the kernel runs under
    ``interpret=True``).  ``kind`` only scopes the error message so a typo in
    any of the knobs reports uniformly.
    """
    if kind not in _IMPL_KINDS:
        raise ValueError(
            f"unknown impl kind {kind!r} (known: {list(_IMPL_KINDS)})"
        )
    if name == "auto":
        return "einsum" if interpret_mode() else "kernel"
    if name not in _IMPL_VALUES:
        raise ValueError(
            f"unknown {kind}_impl {name!r} (expected one of {list(_IMPL_VALUES)})"
        )
    return name


def fedavg_agg(deltas, weights, *, use_pallas: bool = True, interpret: bool | None = None):
    if not use_pallas:
        return ref.fedavg_agg_ref(deltas, weights)
    itp = interpret_mode() if interpret is None else interpret
    return _fedavg_agg_kernel(deltas, weights, interpret=itp)


def pack_codes(codes, *, bits: int, use_pallas: bool = True,
               interpret: bool | None = None):
    """Quantization codes (N, D) -> packed uint8 (compression uplink)."""
    if not use_pallas:
        return ref.pack_codes_ref(codes, bits=bits)
    itp = interpret_mode() if interpret is None else interpret
    return _pack_codes_kernel(codes, bits=bits, interpret=itp)


def unpack_codes(packed, *, bits: int, dim: int, use_pallas: bool = True,
                 interpret: bool | None = None):
    """Packed uint8 -> int32 codes (N, dim)."""
    if not use_pallas:
        return ref.unpack_codes_ref(packed, bits=bits, dim=dim)
    itp = interpret_mode() if interpret is None else interpret
    return _unpack_codes_kernel(packed, bits=bits, dim=dim, interpret=itp)


def topk_select(v, *, k: int, use_pallas: bool = True,
                interpret: bool | None = None):
    """Each row's top-k threshold ``(thr, last)``, (n, 1) int32 each (the
    top-k codec's selection)."""
    if not use_pallas:
        return ref.topk_select_ref(v, k)
    itp = interpret_mode() if interpret is None else interpret
    return _topk_select_kernel(v, k=k, interpret=itp)


def local_sgd(w1, b1, w2, b2, x, y, act, mask, *, lr: float, batch_size: int,
              epochs: int, use_pallas: bool = True,
              interpret: bool | None = None):
    """Fused per-client local SGD over a block of clients (the FedAR
    ClientUpdate hot path); ``use_pallas=False`` vmaps the pure-jnp oracle."""
    if not use_pallas:
        one = functools.partial(
            ref.local_sgd_ref, lr=lr, batch_size=batch_size, epochs=epochs
        )
        return jax.vmap(
            lambda xi, yi, ai, mi: one(w1, b1, w2, b2, xi, yi, ai, mi)
        )(x, y, act, mask)
    itp = interpret_mode() if interpret is None else interpret
    return _local_sgd_kernel(
        w1, b1, w2, b2, x, y, act, mask, lr=lr, batch_size=batch_size,
        epochs=epochs, interpret=itp,
    )


def flash_attention(q, k, v, *, causal=True, window=0, use_pallas: bool = True,
                    interpret: bool | None = None):
    if not use_pallas:
        return ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    itp = interpret_mode() if interpret is None else interpret
    return _flash_kernel(q, k, v, causal=causal, window=window, interpret=itp)


def ssm_scan(xd, logdecay, Bc, Cc, *, use_pallas: bool = True,
             interpret: bool | None = None, **kw):
    if not use_pallas:
        return ref.ssm_scan_ref(xd, logdecay, Bc, Cc).astype(xd.dtype)
    itp = interpret_mode() if interpret is None else interpret
    return _ssm_kernel(xd, logdecay, Bc, Cc, interpret=itp, **kw)
