"""The correctness check fails what it must: the controls (the reference
put in the program's place with float8 matmul operands, or held in
bfloat16) and a run with the timed path broken underneath.  A six-robot
fleet on the CPU, held to the limits of the 2,048-client cell; the
harness's look for a chip is skipped."""
import json
import time
from pathlib import Path

import jax.numpy as jnp
import pytest

import harness

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
SEED = 2**33 + 17
DEVICE = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}
TINY = {
    "profiles": [[list(range(10)), act, 40] for act in (1, 0, 1, 0, 1, 0)],
    "poisoners": [5],
    "flip_frac": 0.6,
    "eval_samples": 64,
    # every robot is selected, so each fault below touches the aggregate
    "fed": {"defense": "foolsgold", "client_fraction": 1.0},
}


def _cell():
    cell = harness.load_cell(ROOT, "resident-qskew2k")
    config = json.loads((HERE / "configs" / "fedar-mlp-resident.json")
                        .read_text())
    return {**cell, "traffic": TINY, "spec": harness.cell_spec(config, TINY)}


def _run(cell):
    return harness.run_cell(cell, SEED, 0.2, False, DEVICE,
                            time.perf_counter(), harness.CompileCounter(),
                            log=lambda s: None)


def test_sound_run_is_correct():
    line = _run(_cell())
    assert line["correct"], line["checks"]
    assert list(line)[-1] == "checks"
    assert line["metrics"]["samples_per_s"]["value"] > 0


def _program_and_reference(cell):
    spec, config = cell["spec"], cell["system"]
    fleet, weights, w0 = harness.draw(cell, SEED)
    system = config.build(spec, fleet, weights)
    prog = system.checked(harness.CHECKED_ROUNDS)
    system.close()
    ref = config.reference(fleet, spec, w0, prog)
    limits = {k: v for k, v in cell["limits"].items()
              if k != "window_compiles"}  # no window here
    assert not harness.failed_checks(config.compare(prog, ref, w0), limits)
    return fleet, w0, prog, ref, limits


def _control_fails(precision):
    cell = _cell()
    config = cell["system"]
    fleet, w0, prog, ref, limits = _program_and_reference(cell)
    ctl = config.reference(fleet, cell["spec"], w0, prog,
                           precision=precision)
    assert harness.failed_checks(config.compare(ctl, ref, w0), limits)


def test_control_is_not_correct():
    _control_fails("fp8")  # the matmul operands one step below bfloat16


def test_bfloat16_control_is_not_correct():
    _control_fails("bfloat16")  # the state one step below float32


def _unchanged(monkeypatch):
    from repro.core.engine import FedAREngine

    real = FedAREngine._round_step

    def step(self, state, *a, **kw):
        new, out = real(self, state, *a, **kw)
        return new._replace(params=state.params), out

    monkeypatch.setattr(FedAREngine, "_round_step", step)


def _half_clients(monkeypatch):
    from repro.core import aggregation

    real = aggregation.fedavg_aggregate

    def agg(g, deltas, weights, mask, **kw):
        mask = mask & (jnp.cumsum(mask) % 2 == 1)
        return real(g, deltas, weights, mask, **kw)

    monkeypatch.setattr(aggregation, "fedavg_aggregate", agg)


def _client_flipped(monkeypatch):
    from repro.core.engine import FedAREngine

    real = FedAREngine._block_sgd

    def sgd(self, g, fields, m):
        out = real(self, g, fields, m)
        return out.at[0].set(2.0 * g - out[0])  # robot 0's update negated

    monkeypatch.setattr(FedAREngine, "_block_sgd", sgd)


@pytest.mark.parametrize("plant", [_unchanged, _half_clients,
                                   _client_flipped],
                         ids=["state_unchanged", "half_clients",
                              "client_flipped"])
def test_broken_round_is_not_correct(monkeypatch, plant):
    plant(monkeypatch)
    line = _run(_cell())
    assert not line["correct"], line["checks"]
    assert line["failed"] >= 1
