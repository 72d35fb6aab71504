"""What a launch of the serving step costs at the least: model operations
and bytes, counted from the shapes by the architecture's module
(``bench/archs/``), and the chip's least time for them.

A ``Step`` is one launch the window made, as the harness saw it: its
program (``decode`` or ``fused``), for each live lane the KV rows it
already held and the tokens it fed, and the launch's change in each
engine metric that the architecture's module names in its optional
``STEP_COUNTERS`` (say, the experts a launch routed to in each layer,
whose weights alone it read). ``Weights`` holds the bytes of the
prepared quantized matrices as they sit in HBM.
"""
from __future__ import annotations

import dataclasses
import json
import os
from typing import Dict, List, Optional, Tuple

from bench.model import Model

PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "peaks.json")


@dataclasses.dataclass
class Step:
    kind: str                          # 'decode' | 'fused'
    lanes: List[Tuple[int, int]]       # (KV rows held before, tokens fed)
    prompt_tokens: int
    out_tokens: int
    counters: Dict[str, object] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class Weights:
    layer_bytes: Dict[str, int]        # per layer, by matrix name
    lm_head_bytes: int


def peaks(device_kind: str) -> Dict[str, float]:
    """Peak bf16 FLOP/s and HBM bytes/s of one chip of this kind."""
    with open(PEAKS_FILE) as f:
        table = json.load(f)["chips"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def least_s(m: Model, w: Weights, s: Step, pk: Dict[str, float]) -> float:
    """The larger of the launch's model operations over peak FLOP/s and
    its bytes over peak bandwidth, as the architecture's module counts
    them (``step_flops``, ``step_bytes``)."""
    return max(m.arch.step_flops(m, s) / pk["flops"],
               m.arch.step_bytes(m, w, s) / pk["hbm_bytes"])


def mfu(w) -> Optional[float]:
    """Per cent of the window the chip would need at its peaks for the
    window's launches (``least_s`` summed) over the traced window."""
    if not w.steps_ok or not w.steps or w.peaks is None:
        return None
    least = sum(least_s(w.model, w.weights, s, w.peaks) for s in w.steps)
    return 100.0 * least / w.trace.window_s
