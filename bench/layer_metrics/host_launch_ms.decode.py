"""Host time on the critical path per launch, in the decode cells (engine
layer): the mean over the window's launches of the engine's
``engine.iteration`` span less its ``engine.wait`` child, that is the
time the host spends admitting, building inputs, dispatching and
emitting (``bench/engine_trace.py``)."""
from bench import engine_trace


def read(w):
    return engine_trace.host_launch_ms(w)
