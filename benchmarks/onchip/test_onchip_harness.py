"""The harness finds cells, configurations, traffic and metric readers by
name, keeps to the benchmark's naming rules, and refuses to run without a
chip or without the program."""
import json
import os
import re
import shutil
import subprocess
import sys
import time
from pathlib import Path

import pytest

import harness

# a second configuration, as a later change would add it: its sizes, and
# beside them the file that builds its system and holds its reference
TOY_CONFIG = {"name": "toy", "model": {"input_dim": 784, "hidden": 8,
                                       "num_classes": 10},
              "fed": {"local_epochs": 2}}
TOY_SYSTEM = """
import numpy as np


class Toy:
    def __init__(self, fleet):
        self.first = np.arange(fleet.num_clients) < 1

    def round(self):
        return self.first

    def checked(self, rounds):
        return [{"selected": self.round()} for _ in range(rounds)]

    def describe(self):
        return {"engine": "toy"}

    def close(self):
        pass


def build(spec, fleet, weights):
    return Toy(fleet)


def reference(fleet, spec, weights0, prog, precision="float32", fault=None):
    return [{"selected": np.arange(fleet.num_clients) < 1} for _ in prog]


def compare(prog, ref, weights0):
    return {"selection_gap": sum(int((a["selected"] != b["selected"]).sum())
                                 for a, b in zip(prog, ref))}
"""

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_resolves(cell):
    c = harness.load_cell(ROOT, cell)
    for name in ("build", "reference", "compare"):
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


def test_a_second_configuration_is_found_from_files_alone(tmp_path):
    here = tmp_path / "onchip"
    shutil.copytree(HERE, here, ignore=shutil.ignore_patterns("__pycache__"))
    (here / "configs" / "toy.json").write_text(json.dumps(TOY_CONFIG))
    (here / "configs" / "toy.py").write_text(TOY_SYSTEM)
    (here / "traffic" / "pair.json").write_text(json.dumps(
        {"profiles": [[[0, 1], 0, 20], [[2], 1, 30]], "eval_samples": 4}))
    (here / "limits" / "toy-pair.json").write_text(json.dumps(
        {"selection_gap": 0, "window_compiles": 0}))
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append({"name": "toy", "source": "a test",
                             "file": str(here / "configs" / "toy.json"),
                             "reduced": [], "why": "a test"})
    bench["workloads"].append({"name": "toy-pair", "chips": 1,
                               "config": "toy", "traffic": "pair",
                               "why": "a test"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = harness.load_cell(tmp_path, "toy-pair", here=here)
    assert cell["system"].__name__.endswith("toy")
    device = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}
    line = harness.run_cell(cell, 2**33 + 5, 0.05, False, device,
                            time.perf_counter(), harness.CompileCounter(),
                            log=lambda s: None)
    assert line["correct"], line["checks"]
    assert line["attempted"] >= 1
    # client 0's 20 samples, 2 epochs a round
    rate = line["metrics"]["samples_per_s"]["value"]
    assert rate > 0 and line["attempted"] * 40 / rate >= 0.05
    assert list(line["checks"]) == ["selection_gap", "window_compiles"]


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

    mlp = {"input_dim": 784, "hidden": 128, "num_classes": 10}
    trace = types.SimpleNamespace(window_s=2.0)
    r = types.SimpleNamespace(sample_epochs=1000, model=mlp, chips=1,
                              peaks=counting.chip_peaks("TPU v5 lite"),
                              trace=trace, samples_per_s=1.0)
    read = harness.load_reader(HERE, "round.mfu")
    per = counting.flops_per_sample_epoch(mlp)
    assert read(r) == pytest.approx(100 * 1000 * per / (2.0 * 197e12))
    trace.window_s = 0.0
    assert read(r) is None
