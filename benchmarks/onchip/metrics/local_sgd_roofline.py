"""The local-SGD kernel's share of its roofline: the least time the
chips could take for the algorithm's work in the window (the real
sample-epochs' operations and bytes, and each selected client's
parameters in and out, as the configuration counts them:
``counts.local_sgd_work``) over the kernel's device time.  None for a
configuration that runs no such kernel."""
import counting


def read(r):
    kernel_s = r.trace.layer_s.get("local_sgd", 0.0)
    work = r.counts.local_sgd_work
    if kernel_s <= 0 or r.sample_epochs <= 0 or work is None:
        return None
    flops, nbytes = work(r.clients, r.sample_epochs)
    least, _ = counting.roofline_seconds(flops, nbytes, r.peaks)
    return 100.0 * least / r.chips / kernel_s
