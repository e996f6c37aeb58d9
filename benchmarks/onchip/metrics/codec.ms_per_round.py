"""Device milliseconds per round of the ops under the program's uplink
codec scopes, ``codec.encode`` and ``codec.decode`` (``spanreduce``),
averaged over the chips: the encode, the error feedback and the decode,
whichever codec runs and whether or not a kernel of its own does it."""

PHASES = ("codec.encode", "codec.decode")


def read(r):
    s = sum(r.spans.phase_s.get(p, 0.0) for p in PHASES)
    return 1000.0 * s / r.rounds if s > 0 and r.rounds else None
