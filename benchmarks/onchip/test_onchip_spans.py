"""The program-span reduction (``spanreduce``) and the metrics the harness
reads with it, on a synthesised trace: device ops with ``op_name`` stats in two
modules, nested program spans with stats, and Python-frame events over an
idle gap."""
import json
from collections import namedtuple
from pathlib import Path

import pytest

import harness
import spanreduce

Plane = namedtuple("Plane", "name lines")
Line = namedtuple("Line", "name events")
Event = namedtuple("Event", "name start_ns duration_ns stats")
SCOPES = spanreduce.load_scopes()
ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
PER_LAYER = {m["name"] for m in BENCH["per_layer"]}
DEVICE = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
ROUND = "jit(_step_fn)"


def _ms(a, b, name, **stats):
    return Event(name, int(a * 1e6), int((b - a) * 1e6), list(stats.items()))


def _op(a, b, name, op_name=None, module="jit__step_fn"):
    stats = {"hlo_module": module}
    if op_name is not None:
        stats["tf_op"] = op_name
    return _ms(a, b, name, **stats)


def _trace(ops, second_device=None):
    python = [
        _ms(0, 10, "bench.round"),
        _ms(0.5, 9.5, "fedar.round"),
        _ms(0.5, 0.9, "fedar.prepare"),
        _ms(1, 2, "fedar.dispatch", compiles=0),
        _ms(2, 8, "fedar.wait"),
        _ms(8, 9, "fedar.fetch", copies=7, bytes=100, codec_rows_sent=3,
            codec_rows_encoded=8),
        _ms(8.2, 8.6, "_array.py:631 _value"),  # Python frames
        _ms(8.6, 8.7, "fedar.py:213 run_round"),
        _ms(9, 9.5, "fedar.history"),
        _ms(10, 12, "bench.count"),
        _ms(12, 20, "bench.round"),
        _ms(12, 20, "fedar.round"),
        _ms(12, 14, "fedar.dispatch", compiles=0),
        _ms(14, 20, "fedar.fetch", copies=7, bytes=100, codec_rows_sent=5,
            codec_rows_encoded=8),
    ]
    planes = [
        Plane("/host:CPU", [Line("python", python)]),
        Plane("/device:TPU:0", [Line("XLA Ops", ops)]),
    ]
    if second_device is not None:
        planes.append(Plane("/device:TPU:1",
                            [Line("XLA Ops", second_device)]))
    return planes


OPS = [
    _op(2, 4, "%sort.8", f"{ROUND}/codec.encode/top_k"),
    _op(4, 5, "%topk_decode.2", f"{ROUND}/codec.decode/jit(topk_decode)/"
                                "pallas_call"),
    _op(5, 5.5, "%copy.3", "data['packed']['x'][0]"),  # in no phase
    _op(5.5, 6, "%fusion.1",
        f"{ROUND}/local_sgd/vmap(jit(codec.encode))/mul"),  # innermost
    _op(6, 7, "%convert", "jit(convert_element_type)/convert",
        module="jit_convert_element_type"),  # another module
    _op(15, 17, "%fedavg_agg.1", f"{ROUND}/aggregate/pallas_call"),
    _op(25, 27, "%sort.8", f"{ROUND}/codec.encode/top_k"),  # past the window
]


def test_phases_unscoped_and_other_modules():
    r = spanreduce.reduce(_trace(OPS), SCOPES)
    assert r.window_s == pytest.approx(0.020)
    assert r.phase_s == {"codec.encode": pytest.approx(0.0025),
                         "codec.decode": pytest.approx(0.001),
                         "aggregate": pytest.approx(0.002)}
    assert r.unscoped_s == pytest.approx(0.0005)
    assert r.other_s == pytest.approx(0.001)
    assert r.module_s == pytest.approx(0.006)


def test_op_name_from_the_hlo_text():
    ops = [_op(2, 4, '%sort.8 = f32[8] sort(...), metadata={op_name='
                     f'"{ROUND}/codec.encode/sort"}}'),
           _op(4, 5, "%topk_decode.2 = f32[8] custom-call(...)")]
    r = spanreduce.reduce(_trace(ops), SCOPES)
    assert r.phase_s == {"codec.encode": pytest.approx(0.002)}
    assert r.unscoped_s == pytest.approx(0.001)


def test_op_name_from_the_compiled_modules_hlo():
    """A v5e trace: ops named by HLO text alone, modules by the module
    line with the program's id."""
    hlo = ("HloModule jit__step_fn, entry_computation_layout={...}\n"
           "  %sort.8 = f32[8]{0} sort(f32[8]{0} %p), dimensions={0}, "
           f'metadata={{op_name="{ROUND}/codec.encode/top_k" '
           'source_file="x.py" source_line=3}\n'
           "  ROOT %topk_decode.2 = f32[8]{0} custom-call(f32[8]{0} %p), "
           f'metadata={{op_name="{ROUND}/codec.decode/pallas_call"}}\n'
           "HloModule jit_convert, entry_computation_layout={...}\n"
           '  %sort.8 = f32[8]{0} sort(%q), metadata={op_name="x/sort"}\n')
    names = spanreduce.hlo_op_names([hlo])
    assert names[("jit__step_fn", "topk_decode.2")] == (
        f"{ROUND}/codec.decode/pallas_call")
    assert names[("jit_convert", "sort.8")] == "x/sort"
    ops = [_ms(2, 4, "%sort.8 = f32[8]{0} sort(f32[8]{0} %p)"),
           _ms(4, 5, "%topk_decode.2 = f32[8]{0} custom-call(...)"),
           _ms(6, 7, "%sort.8 = f32[8]{0} sort(f32[8]{0} %q)")]
    planes = _trace([])
    planes[1] = Plane("/device:TPU:0", [
        Line("XLA Modules", [_ms(1.5, 5.5, "jit__step_fn(1474345792)"),
                             _ms(5.5, 7.5, "jit_convert(99)")]),
        Line("XLA Ops", ops)])
    r = spanreduce.reduce(planes, SCOPES, op_names=names)
    assert r.phase_s == {"codec.encode": pytest.approx(0.002),
                         "codec.decode": pytest.approx(0.001)}
    assert r.other_s == pytest.approx(0.001)


def test_module_from_the_module_line_when_ops_do_not_name_it():
    ops = [_ms(2, 4, "%sort.8", tf_op=f"{ROUND}/codec.encode/top_k"),
           _ms(4, 5, "%copy.1", tf_op="x")]
    planes = _trace([])
    planes[1] = Plane("/device:TPU:0", [
        Line("XLA Modules", [_ms(1.5, 5.5, "jit__step_fn(7)")]),
        Line("XLA Ops", ops)])
    r = spanreduce.reduce(planes, SCOPES)
    assert r.phase_s == {"codec.encode": pytest.approx(0.002)}
    assert r.unscoped_s == pytest.approx(0.001)


def test_phases_average_over_devices():
    one = [_op(2, 4, "%sort.8", f"{ROUND}/codec.encode/top_k")]
    two = [_op(2, 3, "%sort.8", f"{ROUND}/codec.encode/top_k")]
    r = spanreduce.reduce(_trace(one, two), SCOPES)
    assert r.phase_s["codec.encode"] == pytest.approx(0.0015)


def test_self_time_counts_and_stats():
    r = spanreduce.reduce(_trace(OPS), SCOPES)
    # round 1: 9 ms, children cover 0.4 + 1 + 6 + 1 + 0.5 = 8.9 ms; the
    # Python frames inside fetch are not program spans
    assert r.self_s["fedar.round"] == pytest.approx(0.0001)
    assert r.self_s["fedar.fetch"] == pytest.approx(0.001 + 0.006)
    assert r.self_s["fedar.dispatch"] == pytest.approx(0.003)
    assert r.count == {"fedar.round": 2, "fedar.prepare": 1,
                       "fedar.dispatch": 2, "fedar.wait": 1,
                       "fedar.fetch": 2, "fedar.history": 1}
    assert r.stats["fedar.fetch"] == {"copies": 14, "bytes": 200,
                                      "codec_rows_sent": 8,
                                      "codec_rows_encoded": 16}
    per = r.per_round(2)
    assert per["stats"]["fedar.fetch"]["copies"] == 7
    assert per["phase_ms"]["codec.encode"] == pytest.approx(1.25)


def test_idle_goes_to_the_innermost_program_span():
    r = spanreduce.reduce(_trace(OPS), SCOPES)
    # busy [2, 7] and [15, 17]: gaps [0, 2] (middle 1: the dispatch),
    # [7, 15] (middle 11: the count, in no program span) and [17, 20]
    # (middle 18.5: the second fetch); the Python frames over 8.2-8.7
    # are passed over
    assert r.idle_s == {"fedar.dispatch": pytest.approx(0.002),
                        spanreduce.UNSPANNED: pytest.approx(0.008),
                        "fedar.fetch": pytest.approx(0.003)}


def _metrics(planes):
    """The per-layer metrics of ``BENCHMARK.json`` that the harness reads
    off ``planes`` in the benchmark's cell, two rounds of 1,000
    sample-epochs."""
    cell = harness.load_cell(ROOT, "resident-qskew2k")
    win = {"rounds": 2, "sample_epochs": 1000, "clients": 4}
    r = harness.readings(cell, win, DEVICE, planes)
    return {k: v["value"]
            for k, v in harness.per_layer_metrics(cell, r).items()}


def test_the_span_metrics():
    got = _metrics(_trace(OPS))
    assert set(got) | {"local_sgd_roofline", "local_sgd.ms_per_round",
                       "defense.ms_per_round"} == PER_LAYER
    assert got == {
        # encode 2 + 0.5 ms (the innermost scope), decode 1 ms, 2 rounds
        "codec.ms_per_round": pytest.approx(1.75),
        "codec.useful_row_share": pytest.approx(50.0),
        "round_body.unscoped_ms_per_round": pytest.approx(0.25),
        # self time of all program spans but the wait: 9 + 8 ms of
        # rounds less 6 ms of wait, over 2 rounds
        "host.busy_ms_per_round": pytest.approx(5.5),
        "host.d2h_copies_per_round": pytest.approx(7.0),
        "device.idle_unspanned_share": pytest.approx(40.0),
        # and by op names (``layers.json``): busy [2, 7] and [15, 17]
        "device.idle_share": pytest.approx(65.0),
        "round.mfu": pytest.approx(100 * 1000 * 409_088 / (0.020 * 197e12)),
        "agg.ms_per_round": pytest.approx(1.0),
        "round_body.xla_ms_per_round": pytest.approx(2.5),
    }


def test_the_span_metrics_are_silent_without_program_spans():
    """A program with no scopes, spans or counters gives a trace the span
    metrics find nothing in: each is left out, and none raises."""
    planes = _trace([_op(2, 4, "%sort.8")])
    planes[0] = Plane("/host:CPU", [Line("python", [
        e for e in planes[0].lines[0].events
        if e.name.startswith("bench.")])])
    assert set(_metrics(planes)) == {"device.idle_share", "round.mfu",
                                     "round_body.xla_ms_per_round"}


def test_scope_file_names_the_program_phases():
    """The program's phases are ``scopes.json``'s and those each
    configuration names in its own file, each once."""
    from repro.common.tracing import PHASES

    own = [p for c in BENCH["configs"]
           for p in json.loads((ROOT / c["file"]).read_text())
           .get("phases", [])]
    assert sorted(PHASES) == sorted([*SCOPES["phases"], *own])
    assert len(set(PHASES)) == len(PHASES)
