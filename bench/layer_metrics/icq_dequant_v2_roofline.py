"""Share of its roofline that the ICQ dequant kernel reaches (kernels
layer): the chip's least time for the calls the window made over the
summed device time of the trace's ``icq_dequant_v2`` events.

A call reads the prepared weight as it sits in HBM and writes the dense
K x N weight in float32, the dtype the dequant arm hands to its matmul;
it does no matrix arithmetic, so its least time is its bytes over the
peak bandwidth.
"""
from bench import tracing

KERNEL = "icq_dequant_v2"
OUT_BYTES = 4


def call_work(d_in, d_out, w_bytes):
    """(FLOPs, bytes) of one call."""
    return 0, w_bytes + d_in * d_out * OUT_BYTES


def calls(w, step):
    """(K, N, weight bytes) of the dequant-arm calls of one launch, as
    the architecture's module lists them."""
    return w.model.arch.dequant_calls(w.model, w.weights, step)


def read(w):
    if w.trace is None or w.peaks is None or not w.steps_ok:
        return None
    n, secs = tracing.kernel_s(w.trace, KERNEL)
    cs = [c for s in w.steps for c in calls(w, s)]
    if n == 0 or n != len(cs) or secs <= 0:
        return None
    nbytes = sum(call_work(a, b, wb)[1] for a, b, wb in cs)
    return 100.0 * (nbytes / w.peaks["hbm_bytes"]) / secs
