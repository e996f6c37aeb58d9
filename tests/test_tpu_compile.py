"""Compile every main-path Pallas kernel for a described TPU v5e chip.

Interpret mode (every other kernel test) cannot see what the chip's
compiler refuses: block shapes off the (8, 128) tiling, value slices it has
no lowering for, more VMEM than a kernel may use.  These tests compile each
kernel at the widths the engine runs it at — the paper's 784-128-10 MLP
(D = 101,770), B = 20, a K = 512 cohort, a 2,048-client resident fleet —
for a v5e that is described, not attached, and check that the program
calls the kernel (``tpu_custom_call``).  Nothing runs.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU compiler
library, and under pytest-xdist only the worker that runs this file does.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.compress import pack_codes, unpack_codes
from repro.kernels.defense_sim import sketch_similarity
from repro.kernels.fedavg_agg import fedavg_agg
from repro.kernels.local_sgd import fused_fits_vmem, local_sgd_fused_ragged
from repro.kernels.topk_select import ROWS, topk_select, topk_select_fits_vmem

I, H, C = 784, 128, 10  # MnistConfig(): the paper's client MLP
D = I * H + H + H * C + C  # 101,770 flat params
B, E = 20, 5
K = 512  # cohort rows
N_RESIDENT = 2048
SKETCH = 256  # FedConfig.defense_sketch_dim
f32, i32 = jnp.float32, jnp.int32


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def spec(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])

    def make(shape, dtype=f32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    return make


def _compile(fn, *args):
    """Compile ``fn`` for the described chip; return the program text."""
    lowered = jax.jit(fn).lower(*args)
    lowered.compile()  # raises what the chip's compiler would raise
    return lowered.as_text()


def _params(spec):
    return spec((I, H)), spec((H,)), spec((H, C)), spec((C,))


def _compile_sgd(spec, rows, nb, batch, epochs):
    """Compile the fused local-SGD kernel over ``rows`` clients of ``nb``
    batch tiles each (a dense block: the one-bucket case)."""
    T = rows * nb
    return _compile(
        lambda *a: local_sgd_fused_ragged(*a, lr=0.1, epochs=epochs,
                                          nb_max=nb),
        *_params(spec), spec((T, batch, I)), spec((T, batch), i32),
        spec((T, batch)), spec((rows,), i32), spec((rows,), i32),
        spec((rows,), i32),
    )


@pytest.mark.parametrize("rows,n", [(12, 300), (K, 300)])
def test_local_sgd_fused_compiles(spec, rows, n):
    """A dense client block through the fused kernel: the paper's 12 robots
    at 300 samples, and a K=512 cohort block."""
    text = _compile_sgd(spec, rows, -(-n // B), B, E)
    assert "tpu_custom_call" in text


def _widest_admitted():
    batch = B
    while fused_fits_vmem(batch + 8, I, H, C):
        batch += 8
    return batch


def test_local_sgd_fused_vmem_budget_matches_compiler(spec):
    """``fused_fits_vmem`` holds a batch tile to what the compiler accepts:
    the widest tile it admits compiles under the kernel's VMEM limit, and a
    tile 1.6x wider is refused by the compiler itself."""
    widest = _widest_admitted()
    assert widest >= 1000  # far past any client batch size in use

    def compile_batch(batch):
        return _compile_sgd(spec, 4, 3, batch, 1)

    assert "tpu_custom_call" in compile_batch(widest)
    too_wide = int(widest * 1.6) // 8 * 8
    assert not fused_fits_vmem(too_wide, I, H, C)
    with pytest.raises(Exception, match="vmem"):
        compile_batch(too_wide)


def test_local_sgd_fused_ragged_compiles(spec):
    """The one-launch ragged kernel over a packed cohort: K clients, up to
    59 batch tiles each (a 1,166-sample quantity-skew client)."""
    T = K * 30
    text = _compile(
        lambda *a: local_sgd_fused_ragged(*a, lr=0.1, epochs=E, nb_max=59),
        *_params(spec), spec((T, B, I)), spec((T, B), i32), spec((T, B)),
        spec((K,), i32), spec((K,), i32), spec((K,), i32),
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("rows", [K, N_RESIDENT])
def test_fedavg_agg_with_staleness_compiles(spec, rows):
    text = _compile(
        lambda d, w, s: fedavg_agg(d, w, staleness=s),
        spec((rows, D)), spec((rows,)), spec((rows,)),
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("rows", [K, N_RESIDENT])
def test_sketch_similarity_compiles(spec, rows):
    text = _compile(sketch_similarity, spec((rows, SKETCH)),
                    spec((rows, SKETCH)))
    assert "tpu_custom_call" in text


def test_pack_unpack_codes_4bit_compile(spec):
    text = _compile(lambda c: pack_codes(c, bits=4), spec((K, D), i32))
    assert "tpu_custom_call" in text
    text = _compile(
        lambda p: unpack_codes(p, bits=4, dim=D),
        spec((K, (D + 1) // 2), jnp.uint8),
    )
    assert "tpu_custom_call" in text


def _row_sorts(text, rows):
    """The ``(values, indices)`` sorts over whole ``(rows, D)`` rows."""
    return re.findall(
        rf"= \(f32\[{rows},{D}\]\S* s32\[{rows},{D}\]\S*\) sort\(", text
    )


@pytest.mark.parametrize("rows", [K, N_RESIDENT])
def test_topk_codec_compiles_with_selection_kernel(spec, rows, monkeypatch):
    """The default top-k codec (D // 32 kept coordinates) at cohort and
    resident fleet scale: the selection kernel and the kept mask, with no
    sort over the rows and no scatter."""
    import dataclasses

    from repro.common.config import FedConfig
    from repro.core.compress import make_compression
    from repro.kernels import ops

    monkeypatch.setattr(ops, "interpret_mode", lambda: False)
    fed = dataclasses.replace(FedConfig(), compress="topk", defense="none",
                              compress_impl="kernel")
    codec = make_compression(fed, D)
    text = jax.jit(lambda *a: codec.roundtrip(*a)[:2]).lower(
        spec((rows, D)), spec((rows, D)), spec((rows,), jnp.bool_), None
    ).compile().as_text()
    assert "tpu_custom_call" in text and " scatter(" not in text
    assert _row_sorts(text, rows) == []


def test_topk_select_vmem_budget_matches_compiler(spec):
    """The widest row the selection kernel admits compiles for the chip,
    and an explicit ``compress_impl="kernel"`` one block wider raises,
    where ``auto`` takes the ``jax.numpy`` bisection."""
    import dataclasses

    from repro.common.config import FedConfig
    from repro.core.compress import make_compression

    wide = max(d for d in range(1024, 1 << 20, 1024)
               if topk_select_fits_vmem(d))
    text = _compile(lambda v: topk_select(v, k=wide // 32),
                    spec((ROWS, wide)))
    assert "tpu_custom_call" in text
    fed = dataclasses.replace(FedConfig(), compress="topk", defense="none",
                              compress_impl="kernel")
    assert not topk_select_fits_vmem(wide + 1)
    with pytest.raises(ValueError, match="VMEM"):
        make_compression(fed, wide + 1)
    auto = make_compression(dataclasses.replace(fed, compress_impl="auto"),
                            wide + 1)
    assert auto.route(wide + 1) == "einsum"


def test_topk_round_decodes_by_kept_mask(spec, monkeypatch):
    """A top-k round of 32 published-width clients compiled for the chip
    decodes its uplink by the kept mask: no ``topk_decode`` kernel, no
    sort over ``(rows, D)``, and the selection kernel in its text."""
    import numpy as np

    import repro.core.aggregation
    import repro.core.foolsgold
    import repro.models.mnist
    from repro.configs.fedar_mnist import CONFIG, fleet_fed
    from repro.core.engine import FedAREngine
    from repro.core.resources import TaskRequirement
    from repro.data.federated import scaled_fleet
    from repro.kernels import ops

    # the backend here is the CPU; the round is traced as the chip runs it
    for mod in (ops, repro.models.mnist, repro.core.foolsgold,
                repro.core.aggregation):
        monkeypatch.setattr(mod, "interpret_mode", lambda: False)
    rows = 32
    fed = fleet_fed(rows, local_epochs=1, compress="topk", defense="none")
    engine = FedAREngine(CONFIG, fed, TaskRequirement())
    assert engine.dim == D
    data = scaled_fleet(rows, samples_per_client=40)

    def described(a):
        return spec(np.shape(a), jnp.asarray(a).dtype)

    lowered = engine.lower_step(jax.tree.map(described, engine.init_state()),
                                jax.tree.map(described, data))
    text = lowered.compile().as_text()
    assert "tpu_custom_call" in text and "codec.decode" in text
    assert "topk_decode" not in text
    assert _row_sorts(text, rows) == []
    assert re.search(r"%topk_select\S* = .* custom-call\(", text)
