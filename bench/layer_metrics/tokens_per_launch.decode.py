"""Output tokens per launch in the window, from the engine's step
counters and the harness's token stamps (engine layer)."""


def read(w):
    c = w.counters
    return c["out_tokens"] / c["launches"] if c["launches"] else None
