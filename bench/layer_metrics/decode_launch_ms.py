"""Device time of one execution of the decode-step program (step
programs layer): the mean over the trace's program executions that ran
the ``paged_attention`` kernel, which only the one-token decode program
calls."""
from bench import tracing


def read(w):
    return tracing.execution_ms(w.trace, "paged_attention")
