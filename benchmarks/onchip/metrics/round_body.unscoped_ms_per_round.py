"""Device milliseconds per round of the round module's ops that lie in
none of the program's phase scopes (``spanreduce``), averaged over the
chips."""


def read(r):
    if not r.spans.phase_s or not r.rounds:
        return None
    return 1000.0 * r.spans.unscoped_s / r.rounds
