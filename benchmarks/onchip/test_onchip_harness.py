"""The harness finds cells, configurations, traffic and metric readers by
name, keeps to the benchmark's naming rules, and refuses to run without a
chip or without the program."""
import hashlib
import json
import os
import re
import shutil
import subprocess
import sys
import time
from collections import namedtuple
from pathlib import Path

import numpy as np
import pytest

import harness

# a second configuration, as a later change would add it: its sizes, its own
# phase scope and kernel layer, and beside them the file that draws its
# fleet and weights, counts its work, builds its system and holds its
# reference.  A token-sequence client family shaped like a mixture-of-
# experts language model: nothing of the MLP fits it.
TOY_CONFIG = {"name": "toy-lm",
              "model": {"vocab_size": 64, "seq_len": 8, "hidden_size": 16,
                        "num_experts": 4},
              "fed": {"local_epochs": 2},
              "phases": ["routing"],
              "layers": {"experts": ["^%toy_experts"]}}
TOY_SYSTEM = """
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

import counting
import fleetgen


@dataclass
class Fleet:
    tokens: np.ndarray  # (sequences, seq_len) int32, client by client
    sizes: np.ndarray  # (N,) sequences per client

    @property
    def num_clients(self):
        return len(self.sizes)


def make_fleet(traffic, seed, spec):
    sizes = fleetgen.layout(traffic).sizes
    m = spec["model"]
    tokens = jax.random.randint(fleetgen.seed_key(seed),
                                (int(sizes.sum()), m["seq_len"]), 0,
                                m["vocab_size"], jnp.int32)
    return Fleet(np.asarray(tokens), sizes)


def init_weights(seed, spec):
    m = spec["model"]
    k1, k2 = jax.random.split(fleetgen.seed_key(seed))
    return {"embed": jax.random.normal(k1, (m["vocab_size"],
                                            m["hidden_size"])),
            "layers": [{"router": jax.random.normal(
                k2, (m["hidden_size"], m["num_experts"]))}]}


def counts(spec):
    m = spec["model"]
    return counting.Counts(
        6 * m["seq_len"] * m["hidden_size"] * m["num_experts"])


def expert_load(weights, tokens):
    with jax.named_scope("routing"):
        logits = weights["embed"][tokens] @ weights["layers"][0]["router"]
        return jax.nn.softmax(logits, -1).sum((0, 1))


class Toy:
    def __init__(self, fleet, weights):
        self.tokens = jnp.asarray(fleet.tokens[:fleet.sizes[0]])
        self.weights = weights
        self.first = np.arange(fleet.num_clients) < 1
        self.step = jax.jit(expert_load)

    def round(self):
        self.load = np.asarray(self.step(self.weights, self.tokens))
        return self.first

    def checked(self, rounds):
        return [{"selected": self.round(), "load": self.load}
                for _ in range(rounds)]

    def describe(self):
        return {"engine": "toy"}

    def close(self):
        del self.weights, self.tokens


def build(spec, fleet, weights):
    return Toy(fleet, weights)


def reference(fleet, spec, weights0, prog, precision="float32", fault=None):
    h = weights0["embed"][fleet.tokens[:fleet.sizes[0]]].astype(np.float64)
    logits = h @ weights0["layers"][0]["router"]
    p = np.exp(logits - logits.max(-1, keepdims=True))
    load = (p / p.sum(-1, keepdims=True)).sum((0, 1))
    return [{"selected": np.arange(fleet.num_clients) < 1, "load": load}
            for _ in prog]


def compare(prog, ref, weights0):
    return {"selection_gap": sum(int((a["selected"] != b["selected"]).sum())
                                 for a, b in zip(prog, ref)),
            "load_gap": max(float(np.abs(a["load"] - b["load"]).max()
                                  / np.abs(b["load"]).max())
                            for a, b in zip(prog, ref))}
"""
TOY_LIMITS = {"selection_gap": 0, "load_gap": 1e-5, "window_compiles": 0}
# the toy's own metrics: its kernel layer's and its phase's device time
TOY_METRICS = {
    "toy.experts_ms_per_round":
        "def read(r):\n"
        "    s = r.trace.layer_s.get('experts', 0.0)\n"
        "    return 1000.0 * s / r.rounds if s > 0 else None\n",
    "toy.routing_ms_per_round":
        "def read(r):\n"
        "    s = r.spans.phase_s.get('routing', 0.0)\n"
        "    return 1000.0 * s / r.rounds if s > 0 else None\n",
}

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
DEVICE = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_resolves(cell):
    c = harness.load_cell(ROOT, cell)
    for name in ("make_fleet", "init_weights", "counts", "build",
                 "reference", "compare"):
        assert callable(getattr(c["system"], name))
    assert set(c["limits"]) >= {"loss_gap", "update_norm_gap",
                                "change_norm_gap", "window_compiles"}
    assert {m["name"] for m in c["end_to_end"]} >= {"samples_per_s",
                                                    "setup_s"}
    for m in c["per_layer"]:
        assert callable(harness.load_reader(HERE, m["name"]))


def test_names_and_units_use_allowed_characters():
    names = [c["name"] for c in BENCH["configs"]]
    for w in BENCH["workloads"]:
        names += [w["name"], w["config"], w["traffic"]]
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        names.append(m["name"])
        assert UNIT.match(m["unit"]), m["unit"]
    for c in BENCH["configs"]:
        names += c["reduced"]
    assert all(NAME.match(n) for n in names), names
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        seen = [x["name"] for x in BENCH[key]]
        assert len(seen) == len(set(seen))


def test_a_new_cell_is_found_from_files_alone(tmp_path):
    here = tmp_path / "onchip"
    shutil.copytree(HERE, here, ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads(json.dumps(BENCH))
    bench["configs"][0]["file"] = str(here / "configs" /
                                      "fedar-mlp-resident.json")
    bench["workloads"].append({"name": "resident-tiny", "chips": 1,
                               "config": "fedar-mlp-resident",
                               "traffic": "tiny", "why": "a test"})
    bench["per_layer"].append({"name": "tiny.metric", "unit": "ms",
                               "better": "lower", "source": "device_trace",
                               "layer": "device", "moves": "samples_per_s",
                               "workloads": ["resident-tiny"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    (here / "traffic" / "tiny.json").write_text(json.dumps(
        {"profiles": [[[0, 1], 0, 20]] * 2, "eval_samples": 4}))
    (here / "limits" / "resident-tiny.json").write_text(json.dumps(
        {"loss_gap": 1.0}))
    (here / "metrics" / "tiny.metric.py").write_text(
        "def read(r):\n    return 42.0\n")
    cell = harness.load_cell(tmp_path, "resident-tiny", here=here)
    assert cell["traffic"]["eval_samples"] == 4
    assert [m["name"] for m in cell["per_layer"]][-1] == "tiny.metric"
    assert harness.load_reader(here, "tiny.metric")(None) == 42.0
    # a metric that lists cells stays out of the others
    other = harness.load_cell(tmp_path, BENCH["workloads"][0]["name"],
                              here=here)
    assert "tiny.metric" not in [m["name"] for m in other["per_layer"]]


def _with_toy(tmp_path, config=TOY_CONFIG):
    """A copy of the benchmark with the toy configuration, a traffic, its
    limits, its two metrics and its cell added as new files and entries
    only; returns the copy's root and its ``onchip`` directory."""
    here = tmp_path / "onchip"
    shutil.copytree(HERE, here, ignore=shutil.ignore_patterns("__pycache__"))
    (here / "configs" / "toy-lm.json").write_text(json.dumps(config))
    (here / "configs" / "toy-lm.py").write_text(TOY_SYSTEM)
    (here / "traffic" / "pair.json").write_text(json.dumps(
        {"profiles": [[[0, 1], 0, 20], [[2], 1, 30]], "eval_samples": 4}))
    (here / "limits" / "toy-pair.json").write_text(json.dumps(TOY_LIMITS))
    bench = json.loads(json.dumps(BENCH))
    bench["configs"][0]["file"] = str(here / "configs" /
                                      "fedar-mlp-resident.json")
    bench["configs"].append({"name": "toy-lm", "source": "a test",
                             "file": str(here / "configs" / "toy-lm.json"),
                             "reduced": [], "why": "a test"})
    bench["workloads"].append({"name": "toy-pair", "chips": 1,
                               "config": "toy-lm", "traffic": "pair",
                               "why": "a test"})
    for name, reader in TOY_METRICS.items():
        (here / "metrics" / f"{name}.py").write_text(reader)
        bench["per_layer"].append({
            "name": name, "unit": "ms", "better": "lower",
            "source": "device_trace", "layer": "toy",
            "moves": "samples_per_s", "workloads": ["toy-pair"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp_path, here


def _toy_run(cell, trace=False):
    return harness.run_cell(cell, 2**33 + 5, 0.05, trace, DEVICE,
                            time.perf_counter(), harness.CompileCounter(),
                            log=lambda s: None)


def test_a_second_configuration_is_found_from_files_alone(tmp_path):
    root, here = _with_toy(tmp_path)
    cell = harness.load_cell(root, "toy-pair", here=here)
    assert cell["system"].__name__.endswith("toy_lm")
    # its own fleet: int32 token sequences on the public layout, ``sizes``
    # counting sequences; its own nested weights
    fleet, _, weights0 = harness.draw(cell, 7)
    assert fleet.tokens.dtype == np.int32 and fleet.tokens.shape == (50, 8)
    assert list(fleet.sizes) == [20, 30]
    assert weights0["layers"][0]["router"].shape == (16, 4)
    assert isinstance(weights0["embed"], np.ndarray)
    line = _toy_run(cell)
    assert line["correct"], line["checks"]
    assert line["attempted"] >= 1
    # client 0's 20 sequences, 2 epochs a round
    rate = line["metrics"]["samples_per_s"]["value"]
    assert rate > 0 and line["attempted"] * 40 / rate >= 0.05
    assert list(line["checks"]) == list(TOY_LIMITS)


# a traced round of either configuration: a toy kernel under the toy's
# phase, an MLP kernel under an MLP phase, and an op in no phase
Plane = namedtuple("Plane", "name lines")
Line = namedtuple("Line", "name events")
Event = namedtuple("Event", "name start_ns duration_ns stats")


def _ev(a, b, name, op_name=None):
    stats = [("hlo_module", "jit_step"), ("tf_op", op_name)] if op_name \
        else []
    return Event(name, int(a * 1e6), int((b - a) * 1e6), stats)


def _planes():
    ops = [_ev(1, 4, "%toy_experts.1 = f32[8,4] custom-call(...)",
               "jit(step)/routing/pallas_call"),
           _ev(4, 5, "%local_sgd_fused_ragged.1 = f32[8] custom-call()",
               "jit(step)/local_sgd/pallas_call"),
           _ev(5, 6, "%fusion.3 = f32[8] fusion()", "jit(step)/mul")]
    host = [_ev(0, 10, "bench.round"), _ev(10, 20, "bench.round")]
    return [Plane("/host:CPU", [Line("python", host)]),
            Plane("/device:TPU:0", [Line("XLA Ops", ops)])]


def test_a_second_configuration_reports_its_own_flops_phase_and_layer(
        tmp_path, monkeypatch):
    import jax

    root, here = _with_toy(tmp_path)
    cell = harness.load_cell(root, "toy-pair", here=here)
    monkeypatch.setattr(jax.profiler, "start_trace", lambda d: None)
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    monkeypatch.setattr(harness.tracereduce, "load_planes",
                        lambda d: _planes())
    line = _toy_run(cell, trace=True)
    assert line["correct"], line["checks"]
    metrics = {k: v["value"] for k, v in line["metrics"].items()}
    rounds = line["attempted"]
    # the toy's FLOPs: 6 * 8 * 16 * 4 a sequence-epoch, 40 of them a round,
    # over the synthetic window's 20 ms
    assert metrics["round.mfu"] == pytest.approx(
        100 * rounds * 40 * 3072 / (0.020 * 197e12))
    assert metrics["toy.experts_ms_per_round"] == pytest.approx(3 / rounds)
    assert metrics["toy.routing_ms_per_round"] == pytest.approx(3 / rounds)
    # no local-SGD kernel count: its roofline reads nothing, though an op
    # of that name ran
    win = {"rounds": 2, "sample_epochs": 1000, "clients": 4}
    r = harness.readings(cell, win, DEVICE, _planes())
    assert r.trace.layer_s["local_sgd"] == pytest.approx(0.001)
    assert harness.load_reader(here, "local_sgd_roofline")(r) is None
    # the MLP cell, on the same trace: neither the toy's layer, nor its
    # phase, nor its metrics; its own FLOPs
    mlp = harness.load_cell(root, BENCH["workloads"][0]["name"], here=here)
    r = harness.readings(mlp, win, DEVICE, _planes())
    assert "experts" not in r.trace.layer_s
    assert set(r.spans.phase_s) == {"local_sgd"}
    # the toy kernel's time is in no phase of the MLP's
    assert r.spans.unscoped_s == pytest.approx(0.004)
    got = harness.per_layer_metrics(mlp, r)
    assert not set(TOY_METRICS) & set(got)
    assert got["round.mfu"]["value"] == pytest.approx(
        100 * 1000 * 409_088 / (0.020 * 197e12))


@pytest.mark.parametrize("own", [{"phases": ["codec.encode"]},
                                 {"layers": {"agg": ["^%toy_agg"]}},
                                 {"layers": {"round_body": ["^%toy"]}}],
                         ids=["phase", "layer", "rest_layer"])
def test_a_configuration_may_not_rename_a_layer_or_phase(tmp_path, own):
    root, here = _with_toy(tmp_path, {**TOY_CONFIG, **own})
    with pytest.raises(ValueError, match="already has"):
        harness.load_cell(root, "toy-pair", here=here)


def _run(cwd, env_extra=None):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(JAX_PLATFORMS="cpu", **(env_extra or {}))
    cell = BENCH["workloads"][0]["name"]
    return subprocess.run(
        [sys.executable, "benchmarks/onchip/run.py", "--workload", cell,
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=240)


def test_run_refuses_the_cpu():
    p = _run(ROOT)
    assert p.returncode != 0
    assert "needs a TPU" in p.stderr
    assert '"correct"' not in p.stdout


def test_run_refuses_a_checkout_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(HERE, tmp_path / "benchmarks" / "onchip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert "no program source" in p.stderr
    assert '"correct"' not in p.stdout


def test_round_mfu_reads_the_traced_window():
    import types

    import counting

    cell = harness.load_cell(ROOT, "resident-qskew2k")
    trace = types.SimpleNamespace(window_s=2.0)
    r = types.SimpleNamespace(sample_epochs=1000, chips=1,
                              counts=cell["system"].counts(cell["spec"]),
                              peaks=counting.chip_peaks("TPU v5 lite"),
                              trace=trace)
    read = harness.load_reader(HERE, "round.mfu")
    assert read(r) == pytest.approx(100 * 1000 * 409_088 / (2.0 * 197e12))
    trace.window_s = 0.0
    assert read(r) is None


# The MLP configuration's fleet and initial weights, drawn through its own
# hooks, as the harness drew them with ``fleetgen`` before configurations
# owned them: integer arrays and every shape by a hash, float arrays by
# their sum and absolute sum (a float bit-pattern may move with the CPU's
# vector width; a changed draw moves these sums by far more).
MLP_PINS = {
    ("tiny", 3): ("2cec38232a170ff5", {
        "eval_x": [206.64249411050696, 4614.8454301095335],
        "resources.bandwidth": [2.4025081396102905, 2.4025081396102905],
        "resources.battery": [1.8904103636741638, 1.8904103636741638],
        "resources.compute": [567.6463012695312, 567.6463012695312],
        "resources.memory": [1068.446533203125, 1068.446533203125],
        "weights.b1": [0.0, 0.0], "weights.b2": [0.0, 0.0],
        "weights.w1": [-32.342634553919225, 4043.4159436828572],
        "weights.w2": [6.8369736095328335, 126.68932303135716],
        "x": [-978.1130760998494, 45773.965600174575]}),
    ("tiny", 2**33 + 5): ("25f193001ed502cd", {
        "eval_x": [-118.39563794638525, 4473.747028571852],
        "resources.bandwidth": [2.4025081396102905, 2.4025081396102905],
        "resources.battery": [1.8904103636741638, 1.8904103636741638],
        "resources.compute": [567.6463012695312, 567.6463012695312],
        "resources.memory": [1068.446533203125, 1068.446533203125],
        "weights.b1": [0.0, 0.0], "weights.b2": [0.0, 0.0],
        "weights.w1": [-12.25057294833568, 4035.004107451352],
        "weights.w2": [-1.8003971712423663, 124.9285524127663],
        "x": [1007.5370196849617, 44757.359812951785]}),
    ("qskew64", 2**31 + 11): ("a3268fe068c0b43c", {
        "eval_x": [-5742.718520766148, 565943.1441499147],
        "resources.bandwidth": [247.98822152987123, 247.98822152987123],
        "resources.battery": [46.227719113230705, 46.227719113230705],
        "resources.compute": [11585.912682533264, 11585.912682533264],
        "resources.memory": [33356.33917236328, 33356.33917236328],
        "weights.b1": [0.0, 0.0], "weights.b2": [0.0, 0.0],
        "weights.w1": [-17.050651645010873, 4046.8424198208713],
        "weights.w2": [-9.725014455492783, 132.57992888535227],
        "x": [-64924.659576268794, 7239813.248562579]}),
}


def _checksum(fleet, weights0):
    arrays = {"x": fleet.x, "y": fleet.y, "sizes": fleet.sizes,
              "offsets": fleet.offsets, "activations": fleet.activations,
              "eval_x": fleet.eval_x, "eval_y": fleet.eval_y,
              **{"resources." + k: v for k, v in fleet.resources.items()},
              **{"weights." + k: v for k, v in weights0.items()}}
    h = hashlib.sha256()
    floats = {}
    for k in sorted(arrays):
        a = np.asarray(arrays[k])
        h.update(f"{k}{a.dtype}{a.shape}".encode())
        if a.dtype.kind == "f":
            a = a.astype(np.float64)
            floats[k] = [float(a.sum()), float(np.abs(a).sum())]
        else:
            h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()[:16], floats


@pytest.mark.parametrize("traffic,seed", list(MLP_PINS))
def test_mlp_fleet_and_weights_are_drawn_as_before(traffic, seed):
    cell = harness.load_cell(ROOT, "resident-qskew2k")
    cell["traffic"] = {
        "tiny": {"profiles": [[[0, 1], 0, 20]] * 2, "eval_samples": 4},
        "qskew64": {**cell["traffic"], "clients": 64}}[traffic]
    fleet, _, weights0 = harness.draw(cell, seed)
    digest, floats = _checksum(fleet, weights0)
    want_digest, want_floats = MLP_PINS[traffic, seed]
    assert digest == want_digest
    assert floats == {k: pytest.approx(v, rel=1e-6)
                      for k, v in want_floats.items()}


@pytest.mark.parametrize("metric", ["round.mfu", "local_sgd_roofline"])
def test_mlp_readers_read_the_counts_as_before(metric):
    """The MLP's counts reach the readers through the configuration's
    ``counts`` and give what the readers computed from ``counting``'s MLP
    functions before, to the bit, on a fixed synthetic trace."""
    import counting

    cell = harness.load_cell(ROOT, "resident-qskew2k")
    win = {"rounds": 3, "sample_epochs": 1_327_485, "clients": 3_071}
    r = harness.readings(cell, win, DEVICE, _planes())
    model = cell["spec"]["model"]
    if metric == "round.mfu":
        flops = r.sample_epochs * counting.flops_per_sample_epoch(model)
        before = 100.0 * flops / (r.trace.window_s * r.chips
                                  * r.peaks.flops_bf16)
    else:
        flops, nbytes = counting.local_sgd_work(r.clients, r.sample_epochs,
                                                model)
        least, _ = counting.roofline_seconds(flops, nbytes, r.peaks)
        before = 100.0 * least / r.chips / r.trace.layer_s["local_sgd"]
    assert harness.load_reader(HERE, metric)(r) == before
