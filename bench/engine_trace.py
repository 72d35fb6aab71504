"""The serving engine's own marks in a traced window.

The engine names its launch phases with host spans (``engine.iteration``
and, under it, ``engine.admit``, ``.inputs``, ``.dispatch``, ``.wait``,
``.emit``, each with its launch number; the iteration and the dispatch
also with the program, a fused dispatch with its token rows) and its
step programs with scopes (``icq_linear``, ``attn_core``, ``sampling``
in each operation's HLO ``op_name``). ``bench/tracing.py`` keeps
neither, so the readers that need them read the window's trace file
here: the spans from the host plane, and each step program's
instruction scopes from the optimized HLO the profiler stores, per
module, in its ``/host:metadata`` plane (the v5e trace's operation events
carry no ``op_name``). A device operation's scope is the innermost of
``SCOPES`` in its ``op_name``; a fusion carries its root's.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
from typing import Dict, Iterator, List, Optional, Tuple

from bench import tracing

SCOPES = ("icq_linear", "attn_core", "sampling")
HLO_PROTO_STAT = "Hlo Proto"
# (name, start_ns, end_ns, stats): an engine.* host span
Span = Tuple[str, float, float, dict]


@dataclasses.dataclass
class Marks:
    spans: List[Span]                     # engine.* spans, by start
    scopes: Dict[str, Dict[str, str]]     # module -> instruction -> scope

    def to_json(self) -> dict:
        return dict(spans=self.spans, scopes=self.scopes)

    @classmethod
    def from_json(cls, d: dict) -> "Marks":
        return cls([tuple(s) for s in d["spans"]], d["scopes"])


def marks(w) -> Marks:
    """The engine's marks of the window a reader gets, read once from the
    harness's trace directory and kept on ``w``."""
    if getattr(w, "marks", None) is None:
        from bench.harness import TRACE_DIR

        w.marks = read(TRACE_DIR) or Marks([], {})
    return w.marks


def read(log_dir: str) -> Optional[Marks]:
    """The marks in the newest trace under ``log_dir`` (None without one)."""
    files = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not files:
        return None
    path = max(files, key=os.path.getmtime)
    from jax.profiler import ProfileData

    spans = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("engine."):
                        spans.append((e.name, e.start_ns, e.end_ns,
                                      dict(e.stats)))
    with open(path, "rb") as f:
        scopes = hlo_scopes(f.read())
    return Marks(sorted(spans, key=lambda s: s[1]), scopes)


# ---------------------------------------------------------------------------
# the step programs' HLO, from the trace's metadata plane
# ---------------------------------------------------------------------------

def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    r = s = 0
    while True:
        b = buf[i]
        i += 1
        r |= (b & 0x7F) << s
        s += 7
        if b < 0x80:
            return r, i


def _fields(buf: bytes) -> Iterator[Tuple[int, object]]:
    """(field number, value) of a serialized protobuf message: ints for
    varints, bytes for length-delimited and fixed-width fields."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            v, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            v, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield key >> 3, v


def scope(op_name: str) -> str:
    """The innermost program scope named in an ``op_name`` path, or ''."""
    for part in reversed(op_name.split("/")):
        if part in SCOPES:
            return part
    return ""


def _module_scopes(hlo_proto: bytes) -> Tuple[str, Dict[str, str]]:
    """(module name, instruction -> scope) of a serialized ``HloProto``
    (xla/service/hlo.proto: HloProto.hlo_module = 1; HloModuleProto.name
    = 1, .computations = 3; HloComputationProto.instructions = 2;
    HloInstructionProto.name = 1, .metadata = 7; OpMetadata.op_name = 2),
    keeping the scoped instructions only."""
    name, out = "", {}
    for f, module in _fields(hlo_proto):
        if f != 1:
            continue
        for g, v in _fields(module):
            if g == 1:
                name = v.decode()
            elif g == 3:
                for h, inst in _fields(v):
                    if h != 2:
                        continue
                    iname, path = "", ""
                    for k, x in _fields(inst):
                        if k == 1:
                            iname = x.decode()
                        elif k == 7:
                            path = next((y.decode() for m, y in _fields(x)
                                         if m == 2), "")
                    sc = scope(path)
                    if sc:
                        out[iname] = sc
    return name, out


def hlo_scopes(xspace: bytes) -> Dict[str, Dict[str, str]]:
    """Module name -> instruction -> scope, for every HLO module whose
    proto a serialized ``XSpace`` holds (tsl/profiler/protobuf/
    xplane.proto: XSpace.planes = 1; XPlane.name = 2, .event_metadata =
    4, .stat_metadata = 5; XEventMetadata.stats = 5; XStatMetadata.name
    = 2; XStat.metadata_id = 1, .bytes_value = 6)."""
    out: Dict[str, Dict[str, str]] = {}
    for f, plane in _fields(xspace):
        if f != 1:
            continue
        name = next((v for g, v in _fields(plane) if g == 2), b"")
        if name != b"/host:metadata":
            continue
        fields = list(_fields(plane))
        stat_names = {}
        for g, entry in fields:
            if g == 5:
                md = dict(_fields(dict(_fields(entry)).get(2, b"")))
                stat_names[md.get(1, 0)] = md.get(2, b"").decode()
        for g, entry in fields:
            if g != 4:
                continue
            for h, stat in _fields(dict(_fields(entry)).get(2, b"")):
                if h != 5:
                    continue
                st = dict(_fields(stat))
                if stat_names.get(st.get(1)) == HLO_PROTO_STAT and 6 in st:
                    name, sc = _module_scopes(st[6])
                    out.setdefault(name, {}).update(sc)
    return out


# ---------------------------------------------------------------------------
# reductions the readers share
# ---------------------------------------------------------------------------

def _inside(w, s: float, e: float) -> bool:
    lo, hi = w.trace.window
    return lo <= s and e <= hi


def host_launch_ms(w) -> Optional[float]:
    """Host time on the critical path per launch: the mean, over the
    window's engine iterations that launched a program, of the
    ``engine.iteration`` span less its ``engine.wait`` children."""
    spans = marks(w).spans
    its = [x for x in spans if x[0] == "engine.iteration"
           and "program" in x[3] and _inside(w, x[1], x[2])]
    if not its:
        return None
    waits = [x for x in spans if x[0] == "engine.wait"]
    own = [(e - s) - sum(b - a for _, a, b, _ in waits if s <= a and b <= e)
           for _, s, e, _ in its]
    return 1e-6 * sum(own) / len(own)


def fused_rows(w) -> Optional[Tuple[int, int]]:
    """(rows computed, rows live) of the window's fused launches, from the
    counts each fused ``engine.dispatch`` span carries (one per launch,
    a degraded retry counted once)."""
    per = {}
    for name, s, e, st in marks(w).spans:
        if (name == "engine.dispatch" and st.get("program") == "fused"
                and "rows" in st and _inside(w, s, e)):
            per.setdefault(st["launch"], (st["rows"], st["live"]))
    if not per:
        return None
    return (sum(r for r, _ in per.values()), sum(v for _, v in per.values()))


def scope_ms(w, program: str, scope_: str) -> Optional[float]:
    """Device time of the operations under program scope ``scope_`` inside
    the window's executions of the program jitted as ``program`` (module
    ``jit_<program>``), over the number of those executions. Container
    operations are left out, so each operation counts once. None where
    the trace holds no such execution or the module no scoped operation."""
    module = "jit_" + program
    names = marks(w).scopes.get(module)
    t = w.trace
    runs = [m for m in t.clip(t.modules)
            if m[0].split("(", 1)[0] == module] if names else []
    if not runs:
        return None
    xs = sorted((s, e) for n, s, e in t.clip(t.ops)
                if names.get(n) == scope_
                and tracing.op_kind(n) not in tracing.CONTAINERS)
    starts = [s for s, _ in xs]
    total = 0.0
    for _, ms, me in runs:
        i = bisect.bisect_left(starts, ms)
        while i < len(xs) and xs[i][0] < me:
            total += min(xs[i][1], me) - xs[i][0]
            i += 1
    return 1e-6 * total / len(runs)
