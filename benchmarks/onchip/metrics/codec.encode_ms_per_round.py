"""Device milliseconds per round of the ops under the program's
``codec.encode`` phase scope (``spanreduce``), averaged over the chips."""


def read(r):
    spans = getattr(r, "spans", None)
    s = spans.phase_s.get("codec.encode", 0.0) if spans else 0.0
    return 1000.0 * s / r.rounds if s > 0 and r.rounds else None
