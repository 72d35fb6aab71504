"""The serving engine's spans, program names, program scopes and fused-row
counters, read the way the benchmark reads them: host spans from the
profiler's own trace, scopes from the compiled programs' HLO metadata."""
import glob
import os
import re

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs import get_config, smoke_variant
from repro.models import init_model
from repro.serving import GenerationEngine, Request

PHASES = ("engine.admit", "engine.inputs", "engine.dispatch", "engine.wait",
          "engine.emit")
B, CHUNK = 2, 4


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """A paged, fused-chunk engine run under the profiler: the engine,
    its host spans, and the arguments of the last call of each step
    program."""
    cfg = smoke_variant(get_config("llama3.2-1b"))
    params = init_model(jax.random.PRNGKey(0), cfg)
    eng = GenerationEngine(params, cfg, batch_size=B, max_len=32,
                           mode="continuous", prefill_chunk=CHUNK,
                           kv_layout="paged", kv_block_size=4,
                           weight_cache="none")
    calls = {}
    lens = []
    for attr in ("_fused_greedy", "_step_greedy"):
        prog = getattr(eng, attr)

        def record(*a, _prog=prog, _attr=attr, **k):
            calls[_attr] = (_prog, a, k)
            if _attr == "_fused_greedy":
                lens.append(np.asarray(a[4]))
            return _prog(*a, **k)
        setattr(eng, attr, record)
    rng = np.random.default_rng(0)
    for i, n in enumerate((9, 3, 6)):
        eng.submit(Request(i, rng.integers(0, cfg.vocab_size, n)
                           .astype(np.int32), max_new_tokens=3))
    d = str(tmp_path_factory.mktemp("trace"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(d, profiler_options=opts)
    try:
        eng.run()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(d, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    spans = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("engine."):
                        spans.append((e.name, e.start_ns, e.end_ns,
                                      dict(e.stats)))
    return eng, sorted(spans, key=lambda s: s[1]), calls, lens


def test_every_launch_has_its_iteration_span_and_five_phases(traced):
    eng, spans, _, _ = traced
    m = eng.metrics
    launches = sorted({s[3]["launch"] for s in spans
                       if s[0] == "engine.dispatch"})
    assert len(launches) == m.fused_steps + m.decode_steps >= 4
    for n in launches:
        its = [s for s in spans if s[0] == "engine.iteration"
               and s[3].get("launch") == n]
        assert len(its) == 1, n
        _, lo, hi, meta = its[0]
        kids = [s for s in spans if s[0] != "engine.iteration"
                and lo <= s[1] and s[2] <= hi]
        assert [s[0] for s in kids] == list(PHASES), n
        assert all(s[3]["launch"] == n for s in kids)
        assert all(a[2] <= b[1] for a, b in zip(kids, kids[1:]))
        prog = kids[2][3]["program"]
        assert prog in ("decode", "fused") and meta["program"] == prog
    progs = [s[3]["program"] for s in spans if s[0] == "engine.dispatch"]
    assert progs.count("fused") == m.fused_steps >= 1
    assert progs.count("decode") == m.decode_steps >= 1


def test_step_programs_are_named_and_scoped(traced):
    eng, _, calls, _ = traced
    names = [p.__name__ for p in (
        eng._step, calls["_step_greedy"][0], eng._step_xla,
        eng._step_greedy_xla, eng._fused, calls["_fused_greedy"][0],
        eng._fused_xla, eng._fused_greedy_xla, eng._decode)]
    assert names == ["decode_step_sampled", "decode_step",
                     "decode_step_sampled_xla", "decode_step_xla",
                     "fused_step_sampled", "fused_step",
                     "fused_step_sampled_xla", "fused_step_xla",
                     "wave_decode_step"]
    for attr, module in (("_step_greedy", "jit_decode_step"),
                         ("_fused_greedy", "jit_fused_step")):
        prog, a, k = calls[attr]
        hlo = prog.lower(*a, **k).compile().as_text()
        assert hlo.startswith(f"HloModule {module},")
        paths = set(re.findall(r'op_name="([^"]*)"', hlo))
        for scope in ("icq_linear", "attn_core", "sampling"):
            assert any(f"/{scope}/" in p for p in paths), (module, scope)


def test_fused_rows_count_rows_and_live_rows(traced):
    eng, _, _, lens = traced
    m = eng.metrics
    assert len(lens) == m.fused_steps >= 1
    assert m.fused_rows == m.fused_steps * B * CHUNK
    assert m.fused_rows_live == sum(int(x.sum()) for x in lens)
    assert 0 < m.fused_rows_live < m.fused_rows
    s = m.summary()
    assert (s["fused_rows"], s["fused_rows_live"]) == (
        m.fused_rows, m.fused_rows_live)
