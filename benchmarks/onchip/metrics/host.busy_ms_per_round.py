"""Host milliseconds per round in the program's own spans, waiting for
the device left out: the self time of every program span but
``fedar.wait`` (``spanreduce``)."""


def read(r):
    spans = getattr(r, "spans", None)
    if not spans or not spans.self_s or not r.rounds:
        return None
    busy = sum(s for name, s in spans.self_s.items()
               if name != "fedar.wait")
    return 1000.0 * busy / r.rounds
