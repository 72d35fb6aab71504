"""Share of its roofline that the fused ICQ matmul kernel reaches
(kernels layer): the chip's least time for the calls the window made
over the summed device time of the trace's ``icq_matmul_v2`` events.

Each call is counted from the logical operation: an M x K by K x N
matmul against the prepared weight as it sits in HBM (its bytes are
``bits_per_weight`` x K x N / 8), plus the activations in and out.
"""
from bench import tracing

KERNEL = "icq_matmul_v2"


def call_work(M, d_in, d_out, w_bytes, act_bytes):
    """(FLOPs, bytes) of one call."""
    return 2 * M * d_in * d_out, w_bytes + M * (d_in + d_out) * act_bytes


def calls(w, step):
    """(M, K, N, weight bytes) of the fused-arm calls of one launch, as
    the architecture's module lists them."""
    return w.model.arch.matmul_calls(w.model, w.weights, step, w.batch)


def read(w):
    if w.trace is None or w.peaks is None or not w.steps_ok:
        return None
    n, secs = tracing.kernel_s(w.trace, KERNEL)
    cs = [c for s in w.steps for c in calls(w, s)]
    if n == 0 or n != len(cs) or secs <= 0:
        return None
    flops = nbytes = 0
    for M, a, b, wb in cs:
        f, by = call_work(M, a, b, wb, w.model.itemsize)
        flops += f
        nbytes += by
    least = max(flops / w.peaks["flops"], nbytes / w.peaks["hbm_bytes"])
    return 100.0 * least / secs
