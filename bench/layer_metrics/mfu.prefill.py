"""Whole-step share of the chip's peak in the prefill cell, read as
``mfu.decode`` is (``bench/work.py``)."""
from bench import work


def read(w):
    return work.mfu(w)
