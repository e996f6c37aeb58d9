"""The local-SGD kernel's share of its roofline: the least time the
chips could take for the algorithm's work in the window (the real
sample-epochs' operations and bytes, and each selected client's
parameters in and out; ``counting.local_sgd_work``) over the kernel's
device time."""
import counting


def read(r):
    kernel_s = r.trace.layer_s.get("local_sgd", 0.0)
    if kernel_s <= 0 or r.sample_epochs <= 0:
        return None
    flops, nbytes = counting.local_sgd_work(r.clients, r.sample_epochs,
                                            r.model)
    least, _ = counting.roofline_seconds(flops, nbytes, r.peaks)
    return 100.0 * least / r.chips / kernel_s
