"""The engine's marks in a trace (``bench/engine_trace.py``) and the
readers built on them: on a trace the profiler writes here on the CPU,
on hand-made windows, and on a few launches of a window recorded on the
chip."""
import importlib.util
import json
import os

import pytest

from bench import engine_trace, model, tracing, work
from bench.harness import Window

HERE = os.path.dirname(os.path.abspath(__file__))
LAYER = os.path.join(HERE, os.pardir, "layer_metrics")
NEW = ("host_launch_ms", "fused_row_use", "fused_linear_ms",
       "fused_attention_ms")
RAG = os.path.join(HERE, "data", "trace_rag.json.gz")
CHAT = os.path.join(HERE, "data", "trace_chat.json")


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "e_" + name.replace(".", "_"), os.path.join(LAYER, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_marks_from_a_trace_the_profiler_writes(tmp_path):
    """Host spans with their stats, and each module's scoped instructions
    from the HLO the profiler keeps in its metadata plane."""
    import jax
    import jax.numpy as jnp

    def fused_step(x):
        with jax.named_scope("icq_linear"):
            y = jnp.tanh(x @ x)
        with jax.named_scope("attn_core"):
            y = jnp.exp(y) * 2.0
        with jax.named_scope("sampling"):
            return jnp.argmax(y, axis=-1)

    f = jax.jit(fused_step)
    x = jnp.ones((16, 16))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("engine.dispatch", launch=3,
                                          program="fused", rows=8, live=2):
            f(x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    m = engine_trace.read(str(tmp_path))
    assert [(s[0], s[3]) for s in m.spans] == [
        ("engine.dispatch", dict(launch=3, program="fused", rows=8,
                                 live=2))]
    sc = m.scopes["jit_fused_step"]
    assert set(sc.values()) == {"icq_linear", "attn_core", "sampling"}
    assert engine_trace.read(str(tmp_path / "none")) is None
    assert engine_trace.scope("jit(f)/while/body/icq_linear/dot") == \
        "icq_linear"
    assert engine_trace.scope("jit(f)/attn_core/icq_linear/x") == \
        "icq_linear"
    assert engine_trace.scope("jit(f)/while/body/add") == ""


def _hand_window():
    """One decode and one fused launch with the engine's spans and the
    fused module's scoped instructions."""
    ms = 1_000_000
    ops = [("fusion.1", 1, 2), ("paged_attention", 2, 4), ("fusion.2", 4, 5),
           ("while.1", 10, 19),                 # a container: left out
           ("icq_dequant_v2.1", 10, 13), ("convolution.1", 13, 15),
           ("fusion.3", 15, 18), ("fusion.4", 18, 19), ("fusion.5", 19, 20)]
    t = tracing.Trace(
        (0, 30 * ms), [(n, a * ms, b * ms) for n, a, b in ops],
        [("jit_decode_step(1)", 1 * ms, 5 * ms),
         ("jit_fused_step(2)", 10 * ms, 20 * ms)], [])
    spans = []
    for n, prog, cuts in ((0, "decode", (0.5, 0.7, 0.9, 1.0, 5.2, 6)),
                          (1, "fused", (7, 8, 9.5, 10, 20.1, 21))):
        spans.append(("engine.iteration", cuts[0] * ms, cuts[-1] * ms,
                      dict(launch=n, program=prog)))
        for name, a, b in zip(("admit", "inputs", "dispatch", "wait",
                               "emit"), cuts, cuts[1:]):
            st = dict(launch=n)
            if name == "dispatch":
                st["program"] = prog
                if prog == "fused":
                    st.update(rows=40, live=10)
            spans.append(("engine." + name, a * ms, b * ms, st))
    scopes = {"jit_fused_step": {
        "while.1": "icq_linear", "icq_dequant_v2.1": "icq_linear",
        "convolution.1": "icq_linear", "fusion.3": "attn_core",
        "fusion.5": "sampling"},
        "jit_decode_step": {"fusion.1": "icq_linear",
                            "paged_attention": "attn_core"}}
    m = model.load(os.path.join(HERE, "data", "tiny.json"))
    return Window(model=m, weights=None, peaks=None, batch=4,
                  seconds=t.window_s, steps=[], steps_ok=True, counters={},
                  trace=t, marks=engine_trace.Marks(spans, scopes))


def test_reductions_on_a_hand_made_window():
    w = _hand_window()
    # (6 - 0.5) - 4.2 and (21 - 7) - 10.1
    assert engine_trace.host_launch_ms(w) == pytest.approx((1.3 + 3.9) / 2)
    assert engine_trace.fused_rows(w) == (40, 10)
    assert engine_trace.scope_ms(w, "fused_step", "icq_linear") == \
        pytest.approx(5.0)
    assert engine_trace.scope_ms(w, "fused_step", "attn_core") == \
        pytest.approx(3.0)
    assert engine_trace.scope_ms(w, "decode_step", "attn_core") == \
        pytest.approx(2.0)
    assert engine_trace.scope_ms(w, "fused_step_xla", "icq_linear") is None


@pytest.mark.parametrize("cell", ["decode", "prefill"])
def test_new_readers_on_a_hand_made_window(cell):
    w = _hand_window()
    vals = {n: reader(f"{n}.{cell}")(w) for n in NEW}
    assert vals == pytest.approx(dict(
        host_launch_ms=2.6, fused_row_use=25.0, fused_linear_ms=5.0,
        fused_attention_ms=3.0))


def _recorded(path):
    import gzip

    from bench import manifest

    with (gzip.open if path.endswith(".gz") else open)(path, "rt") as f:
        d = json.load(f)
    t = tracing.Trace.from_json(d["trace"])
    m = model.load(manifest.config_path("internlm2-2b",
                                        manifest.load(manifest.ROOT)))
    marks = (engine_trace.Marks.from_json(d["marks"]) if "marks" in d
             else engine_trace.Marks([], {}))
    return Window(model=m, weights=work.Weights(**d["weights"]),
                  peaks=work.peaks("TPU v5 lite"), batch=d["batch"],
                  seconds=t.window_s,
                  steps=[work.Step(**s) for s in d["steps"]], steps_ok=True,
                  counters=d["counters"], trace=t, marks=marks)


@pytest.mark.parametrize("cell", ["decode", "prefill"])
def test_new_readers_read_nothing_from_a_program_without_marks(cell):
    """The chat trace was recorded before the engine had spans, program
    names or scopes: each new reader returns None on it."""
    w = _recorded(CHAT)
    assert {n: reader(f"{n}.{cell}")(w) for n in NEW} == dict.fromkeys(NEW)


def test_readers_on_a_rag_trace_with_the_engines_marks():
    """A few launches of a traced ``internlm2-2b.rag`` window (v5e) with
    the engine's marks: every per-layer reader of the cell reads a number,
    and the fused step's linears and attention fit inside its execution."""
    w = _recorded(RAG)
    names = ("prompt_tokens_per_launch.prefill", "fused_launch_ms.prefill",
             "icq_dequant_v2_roofline", "mfu.prefill", "idle_share.prefill")
    names += tuple(f"{n}.prefill" for n in NEW)
    vals = {n: reader(n)(w) for n in names}
    assert all(v is not None for v in vals.values()), vals
    for n in ("icq_dequant_v2_roofline", "mfu.prefill", "idle_share.prefill",
              "fused_row_use.prefill"):
        assert 0 < vals[n] <= 100, (n, vals[n])
    assert (vals["fused_linear_ms.prefill"]
            + vals["fused_attention_ms.prefill"]
            <= vals["fused_launch_ms.prefill"])
    assert 0 < vals["host_launch_ms.prefill"] < vals["fused_launch_ms.prefill"]
    rows, live = engine_trace.fused_rows(w)
    assert rows == w.counters["fused"] * w.batch * 512
    assert live == sum(n for s in w.steps if s.kind == "fused"
                       for _, n in s.lanes)


def test_device_and_engine_spans_share_the_clock():
    """Each program execution falls in its own launch: it starts once the
    host is building that launch's inputs, before its ``engine.dispatch``
    span ends, and it ends before that launch's ``engine.wait`` span
    ends. The two timelines of a v5e trace agree to within about a
    millisecond, so an execution may read as starting up to 0.5 ms
    before its dispatch span does (this trace's window: up to 0.45 ms
    before; a chat window traced on the same chip: 0.42-0.84 ms after)."""
    w = _recorded(RAG)
    runs = sorted(w.trace.modules, key=lambda m: m[1])
    spans = w.marks.spans
    launch = {x[3]["launch"]: x for x in spans
              if x[0] == "engine.dispatch"}
    first = {x[3]["launch"]: x for x in spans if x[0] == "engine.inputs"}
    wait = {x[3]["launch"]: x for x in spans if x[0] == "engine.wait"}
    assert [m[0].split("(", 1)[0] for m in runs] == [
        "jit_" + launch[n][3]["program"] + "_step" for n in sorted(launch)]
    assert sum(m[0].startswith("jit_fused_step") for m in runs) >= 2
    for (_, s, e), n in zip(runs, sorted(launch)):
        assert first[n][1] <= s < launch[n][2]
        assert launch[n][1] - 0.5e6 <= s
        assert e <= wait[n][2]
