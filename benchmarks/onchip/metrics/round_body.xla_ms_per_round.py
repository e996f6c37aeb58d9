"""Device milliseconds per round of the round body's ops outside the
kernels and collectives (selection, gathers, deviation, quarantine, trust,
the eval), averaged over the chips."""


def read(r):
    s = r.trace.layer_s.get("round_body", 0.0)
    return 1000.0 * s / r.rounds if s > 0 and r.rounds else None
