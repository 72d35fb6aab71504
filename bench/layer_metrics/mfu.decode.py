"""Whole-step share of the chip's peak in the decode cells: for every
launch of the window the larger of its model operations over peak FLOP/s
and its bytes (prepared weights plus live KV) over peak bandwidth,
summed, over the window's length (``bench/work.py``)."""
from bench import work


def read(w):
    return work.mfu(w)
