"""Host milliseconds per round in the program's own spans, waiting for
the device left out: the self time of every program span but
``fedar.wait`` (``spanreduce``)."""


def read(r):
    if not r.spans.self_s or not r.rounds:
        return None
    busy = sum(s for name, s in r.spans.self_s.items()
               if name != "fedar.wait")
    return 1000.0 * busy / r.rounds
