"""Device milliseconds per round of the defense layer's ops (the name
patterns in ``layers.json``), averaged over the chips."""


def read(r):
    s = r.trace.layer_s.get("defense", 0.0)
    return 1000.0 * s / r.rounds if s > 0 and r.rounds else None
