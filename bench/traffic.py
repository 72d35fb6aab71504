"""The one traffic generator: a mix is a JSON file of parameters, read here.

A traffic file (``bench/traffic/<name>.json``) holds:

  loop           "closed": as many clients as ``concurrency``, each sending
                 its next request when its last one has ended
  concurrency    clients
  batch          engine lanes
  max_len        cache positions per lane
  prefill_chunk  prompt tokens a lane takes per fused launch
  kv_block_size  rows per paged-KV block
  prompt, output length distributions: {"dist": "uniform", "min", "max"}
                 or {"dist": "lognormal", "median", "sigma", "min", "max"}
  cycle          requests per cycle of sizes (see below)
  preroll_s      seconds run after every lane has its first token and
                 before the window opens
  check_requests requests the correctness check compares
  source         where the length distributions come from

Sizes are stratified, not drawn: a cycle holds the lengths at the
quantiles (i + 0.5) / cycle of each distribution, paired and ordered by
one fixed shuffle. Every seed sends the same sizes in the same order, so
a window does the same work whatever the seed; the seed picks the token
ids alone (and, in ``bench/check.py``, which requests are compared). The
first request of each client gets only a stratified share of its output
budget, so that lanes start out of phase, as they would be in a loop
that has run for a while.
"""
from __future__ import annotations

import dataclasses
import json
import math
from statistics import NormalDist
from typing import List

import numpy as np

LOOPS = ("closed",)
REQUIRED = ("loop", "concurrency", "batch", "max_len", "prefill_chunk",
            "kv_block_size", "prompt", "output", "cycle", "preroll_s",
            "check_requests", "source")
ORDER_SEED = 0       # the one shuffle that pairs and orders the sizes


@dataclasses.dataclass
class Spec:
    loop: str
    concurrency: int
    batch: int
    max_len: int
    prefill_chunk: int
    kv_block_size: int
    prompt: dict
    output: dict
    cycle: int
    preroll_s: float
    check_requests: int
    source: str


@dataclasses.dataclass
class Req:
    idx: int               # order of sending, from 0
    client: int
    prompt: np.ndarray     # int32 token ids
    max_new: int


def load(path: str) -> Spec:
    with open(path) as f:
        d = json.load(f)
    missing = [k for k in REQUIRED if k not in d]
    if missing:
        raise ValueError(f"{path}: missing {missing}")
    spec = Spec(**{k: d[k] for k in REQUIRED})
    validate(spec)
    return spec


def validate(s: Spec) -> None:
    if s.loop not in LOOPS:
        raise ValueError(f"loop must be one of {LOOPS}, got {s.loop!r}")
    if not 1 <= s.concurrency <= s.batch:
        raise ValueError("a closed loop keeps 1 <= concurrency <= batch")
    for what, dist in (("prompt", s.prompt), ("output", s.output)):
        if dist.get("dist") not in ("uniform", "lognormal"):
            raise ValueError(f"{what}: dist must be uniform or lognormal")
        if not 1 <= dist["min"] <= dist["max"]:
            raise ValueError(f"{what}: need 1 <= min <= max")
    if s.prompt["max"] + s.output["max"] > s.max_len:
        raise ValueError("the longest prompt and output must fit max_len")
    if s.cycle < 1 or s.check_requests < 1:
        raise ValueError("cycle and check_requests must be >= 1")
    if not isinstance(s.source, str) or not s.source.strip():
        raise ValueError("source: name where the lengths come from")


def quantile(dist: dict, q: float) -> int:
    """Length at quantile q of a (clipped) distribution."""
    lo, hi = dist["min"], dist["max"]
    if dist["dist"] == "uniform":
        x = lo + q * (hi - lo + 1)
        return int(min(hi, max(lo, math.floor(x))))
    z = NormalDist().inv_cdf(q)
    x = dist["median"] * math.exp(dist["sigma"] * z)
    return int(min(hi, max(lo, round(x))))


def stratified(dist: dict, n: int) -> List[int]:
    return [quantile(dist, (i + 0.5) / n) for i in range(n)]


class Traffic:
    """Request stream for one seed. ``next(client)`` gives that client's
    next request; requests are numbered in sending order. The sizes do
    not depend on the seed; the token ids do."""

    def __init__(self, spec: Spec, seed: int, vocab: int):
        self.spec = spec
        self.vocab = vocab
        self.seed = int(seed)
        self._sent = 0
        self._started = set()
        rng = np.random.default_rng(ORDER_SEED)
        n, c = spec.cycle, spec.concurrency
        prompts = stratified(spec.prompt, n)
        outputs = stratified(spec.output, n)
        pair, order = rng.permutation(n), rng.permutation(n)
        self._cycle = [(prompts[i], outputs[pair[i]]) for i in order]
        shares = [(i + 0.5) / c for i in range(c)]
        self._first_share = [shares[i] for i in rng.permutation(c)]

    def next(self, client: int) -> Req:
        p_len, out = self._cycle[self._sent % len(self._cycle)]
        if client not in self._started:
            self._started.add(client)
            out = max(1, math.ceil(self._first_share[client] * out))
        idx = self._sent
        self._sent += 1
        rng = np.random.default_rng([self.seed, 1, idx])
        prompt = rng.integers(0, self.vocab, p_len, dtype=np.int32)
        return Req(idx, client, prompt, out)
