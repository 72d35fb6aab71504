"""Device time of one execution of the fused prefill+decode program in
the decode cells (step programs layer): the mean over the trace's program
executions that ran the ``icq_dequant_v2`` kernel, which only the fused
program calls."""
from bench import tracing


def read(w):
    return tracing.execution_ms(w.trace, "icq_dequant_v2")
