"""Prompt tokens the window's launches consumed, per launch: the
engine's ``prefill_tokens`` and ``prompt_decode_tokens`` counters over
its launch counters (engine layer)."""


def read(w):
    c = w.counters
    return c["prompt_tokens"] / c["launches"] if c["launches"] else None
