"""Share of the fused step's token rows that held a token, in the decode
cells (engine layer): 100 x the live rows (each launch's ``lens.sum()``)
over the rows computed (batch x chunk a launch), summed over the
window's fused launches from the counts the engine puts on each
launch's ``engine.dispatch`` span (``bench/engine_trace.py``)."""
from bench import engine_trace


def read(w):
    rows = engine_trace.fused_rows(w)
    if not rows or not rows[0]:
        return None
    return 100.0 * rows[1] / rows[0]
