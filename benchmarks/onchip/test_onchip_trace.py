"""The trace-to-metrics reduction on a synthesised trace with fixed device
op intervals and host spans."""
from collections import namedtuple

import pytest

import tracereduce

Plane = namedtuple("Plane", "name lines")
Line = namedtuple("Line", "name events")
Event = namedtuple("Event", "name start_ns duration_ns")

LAYERS = {
    "ops_line": "XLA Ops",
    "layers": {"local_sgd": ["_ragged_kernel"], "agg": ["_agg_kernel"],
               "collective": ["all-reduce"]},
    "rest": "round_body",
}


def _ms(a, b, name):
    return Event(name, int(a * 1e6), int((b - a) * 1e6))


def _trace(device_ops, second_device=None):
    host = Plane("/host:CPU", [
        Line("python", [_ms(0, 10, "bench.round"), _ms(10, 12, "bench.count"),
                        _ms(12, 20, "bench.round"),
                        _ms(13, 15, "host.sample")]),
        Line("other", [_ms(-5, 30, "thread.loop")]),
    ])
    planes = [host, Plane("/device:TPU:0", [
        Line("XLA Modules", [_ms(0, 20, "jit_step")]),
        Line("XLA Ops", device_ops),
    ])]
    if second_device is not None:
        planes.append(Plane("/device:TPU:1", [Line("XLA Ops",
                                                   second_device)]))
    return planes


def test_busy_layers_and_gaps():
    ops = [_ms(1, 6, "_ragged_kernel.3"), _ms(5, 8, "fusion.1"),
           _ms(16, 19, "_agg_kernel"), _ms(25, 27, "fusion.9")]  # last: outside
    r = tracereduce.reduce(_trace(ops), LAYERS)
    assert r.window_s == pytest.approx(0.020)
    assert r.busy_s == [pytest.approx(0.010)]  # [1, 8] and [16, 19]
    assert r.layer_s["local_sgd"] == pytest.approx(0.005)
    assert r.layer_s["agg"] == pytest.approx(0.003)
    assert r.layer_s["round_body"] == pytest.approx(0.003)  # overlap kept
    assert r.top_ops[0] == ["_ragged_kernel.3", pytest.approx(0.005)]
    idle = dict(r.idle_by_host)
    # gaps [0,1] and [19,20] lie in rounds; the middle of [8,16] (12) lies
    # in a round, the count span and a long thread span: innermost wins
    assert idle["bench.round"] == pytest.approx(0.002)
    assert idle["bench.count"] == pytest.approx(0.008)
    assert sum(idle.values()) == pytest.approx(r.window_s - r.busy_s[0])


def test_layers_average_over_devices():
    ops0 = [_ms(0, 10, "_ragged_kernel"), _ms(10, 12, "all-reduce.1")]
    ops1 = [_ms(0, 6, "_ragged_kernel"), _ms(6, 10, "all-reduce.1")]
    r = tracereduce.reduce(_trace(ops0, ops1), LAYERS)
    assert r.layer_s["local_sgd"] == pytest.approx(0.008)
    assert r.layer_s["collective"] == pytest.approx(0.003)
    assert r.busy_s == [pytest.approx(0.012), pytest.approx(0.010)]
    assert r.mean_busy_s == pytest.approx(0.011)


def test_no_window_span_is_an_error():
    planes = [Plane("/device:TPU:0", [Line("XLA Ops", [_ms(0, 1, "x")])])]
    with pytest.raises(ValueError, match="bench.round"):
        tracereduce.reduce(planes, LAYERS)


def test_layer_file_patterns_classify_the_kernels():
    layers = tracereduce.load_layers()
    # op events are named by their HLO instruction, as a v5e trace has them
    sgd = "%local_sgd_fused_ragged.1 = (f32[1024,784,128]) custom-call(...)"
    assert tracereduce.classify(sgd, layers) == "local_sgd"
    assert tracereduce.classify("%pack_codes.2 = u8[2048,25600] custom-"
                                "call(...)", layers) == "codec"
    assert tracereduce.classify("%fusion.12 = f32[8] fusion(%pack_codes.2)",
                                layers) == layers["rest"]
