"""Device time of attention in one execution of the fused
prefill+decode program, in the decode cells (step programs layer): the
operations under program scope ``attn_core`` (KV write and attention,
without the projections) inside executions of module
``jit_fused_step``, over the number of those executions
(``bench/engine_trace.py``)."""
from bench import engine_trace


def read(w):
    return engine_trace.scope_ms(w, "fused_step", "attn_core")
