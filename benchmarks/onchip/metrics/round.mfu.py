"""The whole round's share of the chips' peak, from the trace: the real
sample-epochs the traced window completed, times the configuration's
operations per sample-epoch (``counts``), over the window's length as the
trace has it (first start to last end of the benchmark's ``bench.round``
spans) and the chips' bf16 peak (``counting.PEAKS``)."""


def read(r):
    if r.sample_epochs <= 0 or r.trace.window_s <= 0:
        return None
    flops = r.sample_epochs * r.counts.flops_per_sample_epoch
    return 100.0 * flops / (r.trace.window_s * r.chips * r.peaks.flops_bf16)
