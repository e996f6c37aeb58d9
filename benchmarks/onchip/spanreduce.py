"""From a profiler trace to the program's own phases, spans and counters.

``reduce`` takes the planes ``tracereduce`` reads (each with ``name`` and
``lines``; a line with ``name`` and ``events``; an event with ``name``,
``start_ns``, ``duration_ns`` and ``stats``, pairs of name and value) and
the scope file ``scopes.json``, and returns for the traced window (first
start to last end of the benchmark's ``bench.round`` spans, as in
``tracereduce``):

* each program phase's device seconds, averaged over the devices.  An op
  belongs to the innermost phase scope (``scopes["phases"]``) named in its
  ``op_name``: the op event's ``tf_op`` (or ``op_name``) stat where the
  trace carries one, else an ``op_name="..."`` in its HLO text, else
  ``op_names`` (from the compiled modules' HLO text, ``hlo_op_names``,
  keyed by module and op: a v5e trace names ops by their HLO text with no
  metadata);
* the device seconds of the ops with no phase in the round's module (a
  module any op of which has a phase), and of ops in other modules;
* each program host span's self time (``scopes["span_prefixes"]``): its
  duration less the part its child program spans cover, summed by name,
  with the spans' count and the sums of their numeric stats;
* the device's idle gaps, each put down to the innermost program span that
  covers its middle (Python-frame and runtime events are passed over), or
  to ``UNSPANNED``.
"""
from __future__ import annotations

import json
import re
from collections import defaultdict
from dataclasses import dataclass, field
from pathlib import Path

import tracereduce

SCOPES_FILE = Path(__file__).resolve().parent / "scopes.json"
UNSPANNED = "unspanned"
OP_NAME_STATS = ("tf_op", "op_name")
OP_NAME_TEXT = re.compile(r'op_name="([^"]*)"')
MODULE_LINE = "XLA Modules"
SPAN_NAME = re.compile(r"[\w.]+")
MODULE_ID = re.compile(r"\(\d+\)$")  # "jit__step_fn(1474...)" in a trace
HLO_MODULE = re.compile(r"HloModule ([^\s,]+)")
HLO_OP = re.compile(r'\s*(?:ROOT )?%(\S+) = .*?, metadata=\{op_name="([^"]*)"')


@dataclass
class Spans:
    window_s: float
    phase_s: dict  # phase -> mean device seconds
    unscoped_s: float  # round module's ops with no phase
    other_s: float  # ops of other modules
    self_s: dict  # program span name -> summed self seconds
    count: dict  # program span name -> spans
    stats: dict = field(default_factory=dict)  # span name -> {stat: sum}
    idle_s: dict = field(default_factory=dict)  # span name -> idle seconds

    @property
    def module_s(self) -> float:
        """The round module's device seconds: its phases and the rest."""
        return sum(self.phase_s.values()) + self.unscoped_s

    def per_round(self, rounds: int) -> dict:
        """Milliseconds (and stats) per round, for the log."""
        ms = 1000.0 / rounds
        return {
            "phase_ms": {k: v * ms for k, v in self.phase_s.items()},
            "unscoped_ms": self.unscoped_s * ms,
            "other_ms": self.other_s * ms,
            "module_ms": self.module_s * ms,
            "self_ms": {k: v * ms for k, v in self.self_s.items()},
            "count": {k: v / rounds for k, v in self.count.items()},
            "stats": {k: {s: v / rounds for s, v in st.items()}
                      for k, st in self.stats.items()},
            "idle_ms": {k: v * ms for k, v in self.idle_s.items()},
        }


def load_scopes(path: Path = SCOPES_FILE) -> dict:
    return json.loads(Path(path).read_text())


def phase_of(op_name: str, phases) -> str | None:
    """The innermost of ``phases`` named in ``op_name`` (``jit(f)/a/b``,
    transforms as ``vmap(...)``), or None."""
    found = [p for p in re.split(r"[/()]", op_name or "") if p in phases]
    return found[-1] if found else None


def _stats(ev) -> dict:
    return dict(ev.stats)


def _op_name(ev, stats, module, op_names) -> str | None:
    for key in OP_NAME_STATS:
        if stats.get(key):
            return str(stats[key])
    m = OP_NAME_TEXT.search(ev.name)
    if m:
        return m.group(1)
    op = stats.get("hlo_op") or ev.name.split(" ", 1)[0].lstrip("%")
    return op_names.get((module, op))


def hlo_op_names(hlo_texts) -> dict:
    """``{(module, op): op_name}`` from compiled modules' HLO text."""
    out = {}
    for text in hlo_texts:
        module = None
        for line in text.splitlines():
            m = HLO_MODULE.match(line)
            if m:
                module = m.group(1)
                continue
            m = HLO_OP.match(line)
            if m:
                out[(module, m.group(1))] = m.group(2)
    return out


def _modules(plane):
    """``(start, end, name)`` of the plane's module events, sorted."""
    return sorted((ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                  for line in plane.lines if line.name == MODULE_LINE
                  for ev in line.events)


def _module_at(modules, t):
    for s, e, name in modules:
        if s <= t < e:
            return MODULE_ID.sub("", name)
        if s > t:
            break
    return None


def is_program_span(name: str, prefixes) -> bool:
    """A program span's name: a prefix and dotted words.  A Python frame
    of ``fedar.py`` (``fedar.py:213 run_round``) is none."""
    return name.startswith(prefixes) and SPAN_NAME.fullmatch(name) is not None


def program_spans(planes, prefixes, lo, hi):
    """Program host spans inside ``[lo, hi]`` as ``(start, end, name,
    stats)``, and each one's self nanoseconds, in one list per host line
    (spans of one thread nest; other events are passed over)."""
    out = []
    for p in planes:
        if not p.name.startswith("/host:"):
            continue
        for line in p.lines:
            # by start, the outer of two spans that start together first
            spans = sorted(
                ((ev.start_ns, ev.start_ns + ev.duration_ns, ev.name,
                  _stats(ev))
                 for ev in line.events if is_program_span(ev.name, prefixes)
                 and lo <= ev.start_ns
                 and ev.start_ns + ev.duration_ns <= hi),
                key=lambda sp: (sp[0], -sp[1]))
            selfs = [e - s for s, e, _, _ in spans]
            stack = []
            for i, (s, e, _, _) in enumerate(spans):
                while stack and spans[stack[-1]][1] <= s:
                    stack.pop()
                if stack:
                    selfs[stack[-1]] -= e - s
                stack.append(i)
            out.extend(zip(spans, selfs))
    return out


def reduce(planes, scopes: dict, window_span: str = "bench.round",
           op_names: dict | None = None) -> Spans:
    planes = list(planes)
    phases = set(scopes["phases"])
    prefixes = tuple(scopes["span_prefixes"])
    op_names = op_names or {}
    hosts = tracereduce.host_events(planes)
    wins = [(s, e) for s, e, n in hosts if n == window_span]
    if not wins:
        raise ValueError(f"no host span named {window_span!r} in the trace")
    lo, hi = min(s for s, _ in wins), max(e for _, e in wins)
    devices = [p for p in planes if p.name.startswith("/device:")
               and any(line.name == "XLA Ops" for line in p.lines)]
    phase_ns = defaultdict(float)
    module_ns = defaultdict(lambda: defaultdict(float))  # module -> phase
    gaps = []
    for i, plane in enumerate(devices):
        modules = _modules(plane)
        iv = []
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for ev in line.events:
                s = max(ev.start_ns, lo)
                e = min(ev.start_ns + ev.duration_ns, hi)
                if e <= s:
                    continue
                iv.append((s, e))
                stats = _stats(ev)
                module = stats.get("hlo_module") or _module_at(
                    modules, ev.start_ns)
                ph = phase_of(_op_name(ev, stats, module, op_names), phases)
                module_ns[module][ph] += (e - s) / len(devices)
        if i == 0:
            merged = tracereduce._union(iv)
            edges = [lo] + [x for s, e in merged for x in (s, e)] + [hi]
            gaps = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
    unscoped = other = 0.0
    for by_phase in module_ns.values():
        scoped = any(ph is not None for ph in by_phase)
        for ph, ns in by_phase.items():
            if ph is not None:
                phase_ns[ph] += ns
            elif scoped:
                unscoped += ns
            else:
                other += ns
    spans = program_spans(planes, prefixes, lo, hi)
    self_ns, count = defaultdict(float), defaultdict(int)
    stats = defaultdict(lambda: defaultdict(float))
    for (_, _, name, st), own in spans:
        self_ns[name] += own
        count[name] += 1
        for k, v in st.items():
            if isinstance(v, (int, float)):
                stats[name][k] += v
    idle = defaultdict(float)
    marks = [(s, e, name) for (s, e, name, _), _ in spans]
    for (a, b), name in zip(gaps, tracereduce._innermost(
            marks, [(a + b) / 2 for a, b in gaps])):
        idle[UNSPANNED if name == "untraced" else name] += (b - a) / 1e9
    return Spans(
        window_s=(hi - lo) / 1e9,
        phase_s={k: v / 1e9 for k, v in phase_ns.items()},
        unscoped_s=unscoped / 1e9,
        other_s=other / 1e9,
        self_s={k: v / 1e9 for k, v in self_ns.items()},
        count=dict(count),
        stats={k: dict(v) for k, v in stats.items()},
        idle_s=dict(idle),
    )

