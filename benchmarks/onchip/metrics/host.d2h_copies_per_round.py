"""Device-to-host copies per round: the sum of the ``copies`` stat on the
program's ``fedar.fetch`` spans (``spanreduce``)."""


def read(r):
    fetch = r.spans.stats.get("fedar.fetch", {})
    if "copies" not in fetch or not r.rounds:
        return None
    return fetch["copies"] / r.rounds
