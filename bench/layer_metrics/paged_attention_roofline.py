"""Share of its roofline that the paged-attention decode kernel reaches
(kernels layer): the chip's least time for the calls the window made
over the summed device time of the trace's ``paged_attention`` events.

Each call is counted from the logical operation by the architecture's
module (``paged_attention_calls``): its FLOPs and the bytes it must move
(the live KV rows read once, the queries in and the outputs out).
"""
from bench import tracing

KERNEL = "paged_attention"


def calls(w, step):
    """(FLOPs, bytes) of each paged-attention call of one launch."""
    return w.model.arch.paged_attention_calls(w.model, step, w.batch)


def read(w):
    if w.trace is None or w.peaks is None or not w.steps_ok:
        return None
    n, secs = tracing.kernel_s(w.trace, KERNEL)
    cs = [c for s in w.steps for c in calls(w, s)]
    if n == 0 or n != len(cs) or secs <= 0:
        return None
    flops = sum(f for f, _ in cs)
    nbytes = sum(b for _, b in cs)
    least = max(flops / w.peaks["flops"], nbytes / w.peaks["hbm_bytes"])
    return 100.0 * least / secs
