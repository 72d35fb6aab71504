"""Device time of one execution of the fused prefill+decode program in
the prefill cell (step programs layer); read as ``fused_launch_ms.decode``
is."""
from bench import tracing


def read(w):
    return tracing.execution_ms(w.trace, "icq_dequant_v2")
