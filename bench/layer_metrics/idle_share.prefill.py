"""Share of the traced window in which no operation ran on the device,
in the prefill cell (device layer)."""
from bench import tracing


def read(w):
    return tracing.idle_share(w.trace)
