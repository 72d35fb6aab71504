"""Share of its roofline that the paged-attention decode kernel reaches
(kernels layer): the chip's least time for the calls the window made
over the summed device time of the trace's ``paged_attention`` events.

A call is one layer's attention of one decode launch, counted from the
logical operation: for each live lane, QK^T and PV over its live KV rows
(4 x heads x head_dim x rows operations) and those rows of K and V read
once, plus the float32 queries in and outputs out.
"""
from bench import tracing

KERNEL = "paged_attention"


def call_work(m, kv_lens, batch):
    """(FLOPs, bytes) of one call over lanes with these KV lengths."""
    rows = sum(kv_lens)
    flops = 4 * m.n_heads * m.head_dim * rows
    nbytes = 2 * rows * m.kv_dim * m.itemsize
    nbytes += 2 * batch * m.n_heads * m.head_dim * 4
    return flops, nbytes


def read(w):
    if w.trace is None or w.peaks is None or not w.steps_ok:
        return None
    n, secs = tracing.kernel_s(w.trace, KERNEL)
    dec = [s for s in w.steps if s.kind == "decode"]
    m = w.model
    if n == 0 or n != len(dec) * m.n_layers or secs <= 0:
        return None
    flops = nbytes = 0
    for s in dec:
        f, by = call_work(m, [c + k for c, k in s.lanes], w.batch)
        flops += f * m.n_layers
        nbytes += by * m.n_layers
    least = max(flops / w.peaks["flops"], nbytes / w.peaks["hbm_bytes"])
    return 100.0 * least / secs
