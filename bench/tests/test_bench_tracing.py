"""The reduction from trace to per-layer metrics, on a hand-made trace and
on a small trace recorded on the chip."""
import importlib.util
import os

import pytest

from bench import model, tracing, work
from bench.harness import Window

HERE = os.path.dirname(os.path.abspath(__file__))
LAYER = os.path.join(HERE, os.pardir, "layer_metrics")


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "t_" + name.replace(".", "_"), os.path.join(LAYER, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _hand_trace():
    ms = 1_000_000
    ops = [("fusion.1", 0 * ms, 2 * ms), ("icq_matmul_v2.3", 2 * ms, 5 * ms),
           ("paged_attention", 5 * ms, 6 * ms),   # decode execution
           ("icq_dequant_v2.7", 10 * ms, 14 * ms),
           ("fusion.2", 13 * ms, 16 * ms),         # overlaps the one before
           ("icq_matmul_v2.3", 16 * ms, 17 * ms)]  # fused execution
    modules = [("jit_greedy_step(1)", 0, 6 * ms),
               ("jit_greedy_step(2)", 10 * ms, 17 * ms)]
    host = [("bench.iteration", 6 * ms, 10 * ms),
            ("bench.submit", 7 * ms, 8 * ms)]
    return tracing.Trace((-1 * ms, 20 * ms), ops, modules, host)


def test_union_busy_gaps_and_labels():
    t = _hand_trace()
    assert t.window_s == pytest.approx(0.021)
    assert tracing.busy_s(t) == pytest.approx(0.013)
    gaps = tracing.gaps(t)
    assert [(s / 1e6, e / 1e6) for s, e in gaps] == [(-1, 0), (6, 10),
                                                     (17, 20)]
    assert tracing.label(t, 6e6, 10e6) == "engine iteration (host)"
    assert tracing.label(t, 7.2e6, 7.8e6) == "submitting a request"
    assert tracing.label(t, 17e6, 20e6) == "outside any benchmark span"
    assert tracing.op_kind("icq_matmul_v2.3") == "icq_matmul_v2"
    assert tracing.op_name(
        "%icq_matmul_v2.75 = f32[8,2048]{1,0:T(8,128)S(1)} custom-call("
        "f32[8,2048]{1,0} %reshape.274)") == "icq_matmul_v2.75"
    assert tracing.op_name("fusion.3") == "fusion.3"
    assert tracing.op_kind("fusion.12.3") == "fusion"
    assert tracing.kernel_s(t, "icq_matmul_v2") == (2, pytest.approx(0.004))


def test_executions_are_told_apart_by_their_kernels():
    t = _hand_trace()
    dec = tracing.executions(t, "paged_attention")
    fus = tracing.executions(t, "icq_dequant_v2")
    assert [m[0] for m in dec] == ["jit_greedy_step(1)"]
    assert [m[0] for m in fus] == ["jit_greedy_step(2)"]


def test_breakdown_is_capped_and_sorted():
    b = tracing.breakdown(_hand_trace())
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    secs = [v for _, v in b["device_ops"]]
    assert secs == sorted(secs, reverse=True)
    assert b["idle_gaps"][0] == ["engine iteration (host)",
                                 pytest.approx(0.004)]


def test_json_round_trip(tmp_path):
    t = _hand_trace()
    p = str(tmp_path / "t.json")
    tracing.save(t, p)
    u = tracing.load(p)
    assert u.window == t.window and u.ops == t.ops and u.host == t.host


def _window(t, steps, m):
    layer = {n: a * b // 4 for n, a, b in m.arch.layer_linears(m)}
    return Window(model=m, weights=work.Weights(layer, m.d_model * m.vocab
                                                // 4),
                  peaks={"flops": 197e12, "hbm_bytes": 819e9}, batch=8,
                  seconds=t.window_s, steps=steps, steps_ok=True,
                  counters=dict(launches=len(steps), out_tokens=12,
                                prompt_tokens=300, fused=1, decode=1),
                  trace=t)


def test_readers_on_the_hand_trace():
    m = model.load(os.path.join(HERE, "data", "tiny.json"))
    m1 = model.Model(**{**m.__dict__, "n_layers": 1})
    m1 = model.Model(**{**m1.__dict__})
    t = _hand_trace()
    steps = [work.Step("decode", [(10, 1)] * 8, 0, 8),
             work.Step("fused", [(0, 8)] * 4 + [(5, 1)] * 4, 32, 4)]
    # the hand trace has one decode launch: 1 matmul there would need a
    # one-matrix model, so the matmul reader must refuse the mismatch
    w = _window(t, steps, m1)
    assert reader("icq_matmul_v2_roofline")(w) is None
    assert reader("decode_launch_ms")(w) == pytest.approx(6.0)
    assert reader("fused_launch_ms.decode")(w) == pytest.approx(7.0)
    assert reader("idle_share.decode")(w) == pytest.approx(
        100 * (1 - 13 / 21))
    assert reader("tokens_per_launch.decode")(w) == 6
    assert reader("prompt_tokens_per_launch.prefill")(w) == 150
    v = reader("mfu.decode")(w)
    assert 0 < v < 100
    w.steps_ok = False
    assert reader("mfu.decode")(w) is None


RECORDED = os.path.join(HERE, "data", "trace_chat.json")
PER_LAYER = ("tokens_per_launch.decode", "decode_launch_ms",
             "fused_launch_ms.decode", "icq_matmul_v2_roofline",
             "paged_attention_roofline", "mfu.decode", "idle_share.decode")


def test_readers_on_a_trace_recorded_on_the_chip():
    """A slice of a traced ``internlm2-2b.chat`` window (v5e), with the
    launches the harness recorded for it: every per-layer reader of the
    cell reads a number, and the shares stay within 0..100%."""
    import json

    from bench import manifest

    with open(RECORDED) as f:
        d = json.load(f)
    t = tracing.Trace.from_json(d["trace"])
    steps = [work.Step(**s) for s in d["steps"]]
    m = model.load(manifest.config_path("internlm2-2b",
                                        manifest.load(manifest.ROOT)))
    w = Window(model=m, weights=work.Weights(**d["weights"]),
               peaks=work.peaks("TPU v5 lite"), batch=d["batch"],
               seconds=t.window_s, steps=steps, steps_ok=True,
               counters=d["counters"], trace=t)
    vals = {n: reader(n)(w) for n in PER_LAYER}
    assert all(v is not None for v in vals.values()), vals
    for n in ("icq_matmul_v2_roofline", "paged_attention_roofline",
              "mfu.decode", "idle_share.decode"):
        assert 0 < vals[n] <= 100, (n, vals[n])
    n_mm, _ = tracing.kernel_s(t, "icq_matmul_v2")
    dec = sum(s.kind == "decode" for s in steps)
    assert n_mm == dec * (7 * m.n_layers + 1) + (len(steps) - dec)
    b = tracing.breakdown(t)
    assert b["device_ops"][0][0] == "icq_matmul_v2"
