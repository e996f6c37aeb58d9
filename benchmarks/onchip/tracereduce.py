"""From a profiler trace to the per-layer numbers.

``reduce`` takes planes as ``jax.profiler.ProfileData`` gives them (each
with ``name`` and ``lines``; a line with ``name`` and ``events``; an event
with ``name``, ``start_ns`` and ``duration_ns``) and returns, for the
traced window:

* the window itself: from the first start to the last end of the host
  spans named ``window_span`` (the benchmark's own, one per round);
* each device's busy seconds: the union of its op intervals in the window;
* each layer's device seconds: ops matched to layers by the name patterns
  in ``layers.json``, averaged over the devices; ops that match no pattern
  count to the ``rest`` layer;
* the ten ops that took the most device time (named by the first
  ``NAME_CHARS`` characters of their HLO text), and the device's idle
  gaps, each put down to the innermost host span that covers its middle.
"""
from __future__ import annotations

import heapq
import json
import re
from collections import defaultdict
from dataclasses import dataclass
from pathlib import Path

LAYERS_FILE = Path(__file__).resolve().parent / "layers.json"
NAME_CHARS = 160  # an op's HLO text, cut after its result shape


@dataclass
class Reduced:
    window_s: float
    busy_s: list  # per device
    layer_s: dict  # layer -> mean device seconds
    top_ops: list  # [[name, seconds], ...], most first
    idle_by_host: list  # [[host span, seconds], ...], most first

    @property
    def mean_busy_s(self) -> float:
        return sum(self.busy_s) / len(self.busy_s) if self.busy_s else 0.0


def load_layers(path: Path = LAYERS_FILE) -> dict:
    return json.loads(Path(path).read_text())


def classify(name: str, layers: dict) -> str:
    for layer, patterns in layers["layers"].items():
        if any(re.search(p, name) for p in patterns):
            return layer
    return layers["rest"]


def _union(intervals):
    """Merged, sorted ``(start, end)`` intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def host_events(planes, prefix="/host:"):
    out = []
    for p in planes:
        if not p.name.startswith(prefix):
            continue
        for line in p.lines:
            for ev in line.events:
                out.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                            ev.name))
    return out


def _innermost(hosts, points):
    """For each of ``points`` (sorted), the name of the shortest host
    event that covers it, or ``"untraced"``: one sweep with a heap of the
    events begun so far, keyed by length."""
    events = sorted(hosts)
    heap, i, out = [], 0, []
    for t in points:
        while i < len(events) and events[i][0] <= t:
            s, e, n = events[i]
            heapq.heappush(heap, (e - s, e, n))
            i += 1
        while heap and heap[0][1] < t:
            heapq.heappop(heap)
        out.append(heap[0][2] if heap else "untraced")
    return out


def reduce(planes, layers: dict, window_span: str = "bench.round",
           top: int = 10) -> Reduced:
    planes = list(planes)
    hosts = host_events(planes)
    spans = [(s, e) for s, e, n in hosts if n == window_span]
    if not spans:
        raise ValueError(f"no host span named {window_span!r} in the trace")
    lo, hi = min(s for s, _ in spans), max(e for _, e in spans)
    devices = [p for p in planes if p.name.startswith("/device:")
               and any(line.name == layers["ops_line"] for line in p.lines)]
    busy, layer_ns, op_ns = [], defaultdict(float), defaultdict(float)
    gaps = []
    for i, plane in enumerate(devices):
        ops = [ev for line in plane.lines if line.name == layers["ops_line"]
               for ev in line.events]
        iv = []
        for ev in ops:
            s = max(ev.start_ns, lo)
            e = min(ev.start_ns + ev.duration_ns, hi)
            if e <= s:
                continue
            name = ev.name[:NAME_CHARS]
            op_ns[name] += (e - s) / len(devices)
            layer_ns[classify(ev.name, layers)] += (e - s) / len(devices)
            iv.append((s, e))
        merged = _union(iv)
        busy.append(sum(e - s for s, e in merged) / 1e9)
        if i == 0:
            edges = [lo] + [x for s, e in merged for x in (s, e)] + [hi]
            gaps = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
    idle = defaultdict(float)
    for (a, b), name in zip(gaps, _innermost(hosts, [(a + b) / 2
                                                     for a, b in gaps])):
        idle[name] += (b - a) / 1e9
    rank = sorted(op_ns.items(), key=lambda kv: -kv[1])
    return Reduced(
        window_s=(hi - lo) / 1e9,
        busy_s=busy,
        layer_s={k: v / 1e9 for k, v in layer_ns.items()},
        top_ops=[[n, v / 1e9] for n, v in rank[:top]],
        idle_by_host=[[n, v] for n, v in
                      sorted(idle.items(), key=lambda kv: -kv[1])[:top]],
    )


def load_planes(trace_dir: Path):
    """The planes of the one ``.xplane.pb`` a trace wrote under
    ``trace_dir``."""
    from jax.profiler import ProfileData

    files = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if len(files) != 1:
        raise ValueError(f"expected one .xplane.pb under {trace_dir}, "
                         f"found {len(files)}")
    return ProfileData.from_file(str(files[0])).planes
