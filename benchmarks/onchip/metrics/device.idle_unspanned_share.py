"""Share of the traced window in which the device sat idle while the host
was in none of the program's spans (``spanreduce``): idle the program's
spans cannot name."""
import spanreduce


def read(r):
    spans = r.spans
    if not spans.self_s or spans.window_s <= 0:
        return None
    idle = spans.idle_s.get(spanreduce.UNSPANNED, 0.0)
    return 100.0 * idle / spans.window_s
