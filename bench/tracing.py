"""Profiler capture of the window, and its reduction to plain event lists.

``capture_start``/``capture_stop`` wrap ``jax.profiler``; the window is
marked by a host span ``bench.window`` so that device and host events are
read on the profiler's own clock. ``reduce(dir)`` turns the ``.xplane.pb``
into a ``Trace``: the device's operations and program executions, and the
benchmark's own host spans. The per-layer readers work on a ``Trace``
only, so a trace recorded once (``bench/tests/data``) checks them on the
CPU.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import json
import os
import re
from typing import Dict, List, Optional, Tuple

WINDOW_SPAN = "bench.window"
Interval = Tuple[str, float, float]      # (name, start_ns, end_ns)


@dataclasses.dataclass
class Trace:
    window: Tuple[float, float]          # ns, the bench.window span
    ops: List[Interval]                  # device operations (one chip)
    modules: List[Interval]              # device program executions
    host: List[Interval]                 # bench.* host spans

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def clip(self, xs: List[Interval]) -> List[Interval]:
        lo, hi = self.window
        return [(n, max(s, lo), min(e, hi)) for n, s, e in xs
                if e > lo and s < hi]

    def to_json(self) -> dict:
        return dict(window=list(self.window), ops=self.ops,
                    modules=self.modules, host=self.host)

    @classmethod
    def from_json(cls, d: dict) -> "Trace":
        tup = lambda xs: [tuple(x) for x in xs]  # noqa: E731
        return cls(tuple(d["window"]), tup(d["ops"]), tup(d["modules"]),
                   tup(d["host"]))


def capture_start(log_dir: str) -> None:
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(log_dir, profiler_options=opts)


def capture_stop() -> None:
    import jax

    jax.profiler.stop_trace()


def _newest_xplane(log_dir: str) -> Optional[str]:
    files = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return max(files, key=os.path.getmtime) if files else None


def _line(plane, *names):
    for line in plane.lines:
        if line.name in names:
            return line
    return None


def reduce(log_dir: str) -> Optional[Trace]:
    """Read the newest trace under ``log_dir``; None without a device
    plane or without the window span."""
    from jax.profiler import ProfileData

    path = _newest_xplane(log_dir)
    if path is None:
        return None
    data = ProfileData.from_file(path)
    dev = [p for p in data.planes if p.name.startswith("/device:TPU:")]
    host = [p for p in data.planes if p.name.startswith("/host:")]
    spans: List[Interval] = []
    for plane in host:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("bench."):
                    spans.append((e.name, e.start_ns, e.end_ns))
    win = [s for s in spans if s[0] == WINDOW_SPAN]
    if not dev or not win:
        return None
    plane = sorted(dev, key=lambda p: p.name)[0]
    ops_line = _line(plane, "XLA Ops")
    mod_line = _line(plane, "XLA Modules")
    ops = [] if ops_line is None else [
        (op_name(e.name), e.start_ns, e.end_ns) for e in ops_line.events]
    mods = [] if mod_line is None else [
        (e.name, e.start_ns, e.end_ns) for e in mod_line.events]
    spans = [s for s in spans if s[0] != WINDOW_SPAN]
    return Trace((win[0][1], win[0][2]), ops, mods, sorted(spans,
                                                           key=lambda s: s[1]))


# ---------------------------------------------------------------------------
# reductions shared by the readers
# ---------------------------------------------------------------------------

def union(xs: List[Interval]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for _, s, e in sorted(xs, key=lambda x: x[1]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_s(t: Trace) -> float:
    return sum(e - s for s, e in union(t.clip(t.ops))) * 1e-9


def kernel_s(t: Trace, kernel: str) -> Tuple[int, float]:
    """(calls, seconds) of one kernel's events inside the window."""
    xs = [x for x in t.clip(t.ops) if op_kind(x[0]) == kernel]
    return len(xs), sum(e - s for _, s, e in xs) * 1e-9


_SUFFIX = re.compile(r"[.:]\d+$")
_HLO = re.compile(r"^%([^ ]+) = ")


def op_name(event_name: str) -> str:
    """The HLO instruction's name: the TPU trace names an operation by
    its whole HLO text ('%icq_matmul_v2.75 = f32[8,2048] custom-call(...)')."""
    m = _HLO.match(event_name)
    return m.group(1) if m else event_name


def op_kind(name: str) -> str:
    """An operation's name without its instance number ('fusion.12' ->
    'fusion')."""
    while True:
        base = _SUFFIX.sub("", name)
        if base == name:
            return name
        name = base


def executions(t: Trace, kernel: str) -> List[Interval]:
    """Program executions in the window that ran ``kernel`` at least once."""
    ops = [x for x in t.ops if op_kind(x[0]) == kernel]
    starts = sorted(s for _, s, _ in ops)
    out = []
    for m in t.clip(t.modules):
        i = bisect.bisect_left(starts, m[1])
        if i < len(starts) and starts[i] < m[2]:
            out.append(m)
    return out


def execution_ms(t: Trace, kernel: str) -> Optional[float]:
    """Mean device time of the program executions that ran ``kernel``."""
    xs = executions(t, kernel)
    if not xs:
        return None
    return 1e3 * sum((e - s) * 1e-9 for _, s, e in xs) / len(xs)


def idle_share(t: Trace) -> Optional[float]:
    """Per cent of the window in which no operation ran on the device."""
    if t.window_s <= 0:
        return None
    return 100.0 * (1.0 - busy_s(t) / t.window_s)


def gaps(t: Trace) -> List[Tuple[float, float]]:
    """Idle intervals of the device inside the window."""
    lo, hi = t.window
    out, cur = [], lo
    for s, e in union(t.clip(t.ops)):
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if hi > cur:
        out.append((cur, hi))
    return out


def label(t: Trace, s: float, e: float) -> str:
    """What the host was doing during an idle interval: the benchmark
    span that overlaps most of it (the innermost one on a tie)."""
    best: Optional[Interval] = None
    key = None
    for x in t.host:
        lap = min(e, x[2]) - max(s, x[1])
        if lap > 0 and (key is None or (lap, x[1] - x[2]) > key):
            best, key = x, (lap, x[1] - x[2])
    if best is None:
        return "outside any benchmark span"
    return {"bench.iteration": "engine iteration (host)",
            "bench.submit": "submitting a request",
            "bench.on_token": "token callback"}.get(best[0], best[0])


CONTAINERS = ("while", "conditional", "call")   # ops that hold other ops


def breakdown(t: Trace) -> Dict[str, list]:
    per: Dict[str, float] = {}
    for n, s, e in t.clip(t.ops):
        k = op_kind(n)
        if k in CONTAINERS:
            continue
        per[k] = per.get(k, 0.0) + (e - s) * 1e-9
    ops = sorted(per.items(), key=lambda kv: -kv[1])[:10]
    idle = sorted(((label(t, s, e), (e - s) * 1e-9) for s, e in gaps(t)),
                  key=lambda x: -x[1])[:10]
    return dict(device_ops=[[k, v] for k, v in ops],
                idle_gaps=[[k, v] for k, v in idle])


def save(t: Trace, path: str) -> None:
    with open(path, "w") as f:
        json.dump(t.to_json(), f)


def load(path: str) -> Trace:
    with open(path) as f:
        return Trace.from_json(json.load(f))
