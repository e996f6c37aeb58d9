"""Share of the rows the uplink codec encoded that were transmitted: the
sums of the ``codec_rows_sent`` and ``codec_rows_encoded`` stats on the
program's ``fedar.fetch`` spans (``spanreduce``)."""


def read(r):
    fetch = r.spans.stats.get("fedar.fetch", {})
    encoded = fetch.get("codec_rows_encoded", 0)
    if encoded <= 0:
        return None
    return 100.0 * fetch.get("codec_rows_sent", 0) / encoded
