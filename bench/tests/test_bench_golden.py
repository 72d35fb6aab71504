"""Golden values of the dense GQA block, pinned at the commit before the
block moved into ``bench/archs/dense_gqa.py`` (7ed7c34): the seeded draws,
the reference logits at the tiny size, the work counts, the tree, the
program's config and the three roofline readers' call lists and values,
at the tiny configuration and at both real configurations' shapes (no
weights at published widths, beyond one matrix each). Every value must
equal the pinned one to the last digit: the move changed no number."""
import gzip
import hashlib
import importlib.util
import json
import os

import numpy as np
import pytest

from bench import manifest, model, reference, tracing, weights, work
from bench.harness import Window

HERE = os.path.dirname(os.path.abspath(__file__))
TINY = os.path.join(HERE, "data", "tiny.json")
REAL = ("internlm2-2b", "phi3-3b")


def _h(a) -> str:
    return hashlib.sha256(
        np.ascontiguousarray(np.asarray(a)).tobytes()).hexdigest()[:16]


def _hj(x) -> str:
    return hashlib.sha256(json.dumps(x).encode()).hexdigest()[:16]


def _model(name):
    if name == "tiny":
        return model.load(TINY)
    return model.load(manifest.config_path(name, manifest.load()))


def _reader(name):
    path = os.path.join(HERE, os.pardir, "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "golden_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# sha256 (16 hex) of each tiny leaf's float32 draw, by name/layer
TINY_DRAWS = {
    "wq/0": "616a9cc7e6a17b58",
    "wq/1": "88344b916577cb4f",
    "wk/0": "8313f2e193470a36",
    "wk/1": "5e428f0aac0db470",
    "wv/0": "521711a903773c89",
    "wv/1": "88c4785ccd276b68",
    "wo/0": "2084bbe05e92c504",
    "wo/1": "554d546d7cc0e1ca",
    "w_gate/0": "d7dd553e9aff3604",
    "w_gate/1": "d775c24e72920ba7",
    "w_up/0": "1bd113b650659a0e",
    "w_up/1": "be407ca34ac8a4b0",
    "w_down/0": "1a9ca4e8b3b4cb73",
    "w_down/1": "a069373ed171a777",
    "ln1/0": "370b042ca9b2a198",
    "ln1/1": "f5f9a74c95bd33bd",
    "ln2/0": "d2eb455561dbb85d",
    "ln2/1": "9a1f8ea800e80820",
    "embed/0": "82c499c652939439",
    "final_norm/0": "537bf2c375e3ea30",
    "lm_head/0": "b63edee15f43f356",
}


@pytest.mark.parametrize("leaf", sorted(TINY_DRAWS))
def test_tiny_draws_are_the_parents(leaf):
    name, layer = leaf.split("/")
    assert _h(weights.leaf(_model("tiny"), name, int(layer))) == \
        TINY_DRAWS[leaf]


SHAPES = {
    "tiny": {
        "wq": (64, 64),
        "wk": (64, 32),
        "wv": (64, 32),
        "wo": (64, 64),
        "w_gate": (64, 128),
        "w_up": (64, 128),
        "w_down": (128, 64),
        "ln1": (64,),
        "ln2": (64,),
        "embed": (256, 64),
        "final_norm": (64,),
        "lm_head": (64, 256),
    },
    "internlm2-2b": {
        "wq": (2048, 2048),
        "wk": (2048, 1024),
        "wv": (2048, 1024),
        "wo": (2048, 2048),
        "w_gate": (2048, 8192),
        "w_up": (2048, 8192),
        "w_down": (8192, 2048),
        "ln1": (2048,),
        "ln2": (2048,),
        "embed": (92544, 2048),
        "final_norm": (2048,),
        "lm_head": (2048, 92544),
    },
    "phi3-3b": {
        "wq": (3072, 3072),
        "wk": (3072, 3072),
        "wv": (3072, 3072),
        "wo": (3072, 3072),
        "w_gate": (3072, 8192),
        "w_up": (3072, 8192),
        "w_down": (8192, 3072),
        "ln1": (3072,),
        "ln2": (3072,),
        "embed": (32064, 3072),
        "final_norm": (3072,),
        "lm_head": (3072, 32064),
    },
}


@pytest.mark.parametrize("cfg", sorted(SHAPES))
def test_leaf_order_and_shapes_are_the_parents(cfg):
    m = _model(cfg)
    assert [n for n, _ in m.arch.LEAVES] == list(SHAPES[cfg])
    assert {n: tuple(m.arch.leaf_shape(m, n))
            for n, _ in m.arch.LEAVES} == SHAPES[cfg]


# the first leaf (layer 0) drawn at published widths
REAL_FIRST_DRAW = {
    "internlm2-2b": "2316f0c28346e418",
    "phi3-3b": "987ae3c5f15b8418",
}


@pytest.mark.parametrize("cfg", REAL)
def test_first_draw_at_published_widths_is_the_parents(cfg):
    m = _model(cfg)
    assert _h(weights.leaf(m, m.arch.LEAVES[0][0], 0)) == \
        REAL_FIRST_DRAW[cfg]


REF_LOGITS = {
    False: (['e30c6a7617d6c6d2', '94b10109ff041eb5', '4701c8a081d1e66b'],
            [-49.82984762417618, -85.57006338316205, -151.64555087010376]),
    True: (['0fad485ec460b762', 'dab2314816f69b31', '452597cd343948af'],
            [-38.114335761012626, -86.90782272972865, -179.0512991215801]),
}


@pytest.mark.parametrize("lower", [False, True])
def test_reference_logits_are_the_parents(lower):
    m = _model("tiny")
    rng = np.random.default_rng(5)
    seqs = [(rng.integers(0, m.vocab, 20).astype(np.int32), 12),
            (rng.integers(0, m.vocab, 33).astype(np.int32), 20),
            (rng.integers(0, m.vocab, 9).astype(np.int32), 3)]
    z = reference.logits(m, seqs, 48, 16, lower=lower)
    hashes, sums = REF_LOGITS[lower]
    assert [_h(np.asarray(a, np.float32)) for a in z] == hashes
    assert [float(np.asarray(a, np.float64).sum()) for a in z] == sums


def _steps(B=16, S=256):
    return [work.Step("decode", [(100 + 37 * i, 1) for i in range(B)], 0,
                      B),
            work.Step("fused", [(0, S)] + [(5 * i, 1) for i in range(1, B)],
                      S, B - 1),
            work.Step("fused", [(S * (i % 3), S if i % 2 else 1)
                                for i in range(B)], 0, 0),
            work.Step("decode", [(2000 - 90 * i, 1) for i in range(B // 2)],
                      0, B // 2)]


def _weights(m):
    return work.Weights({n: a * b * 3 // 8 + 7
                         for n, a, b in m.arch.layer_linears(m)},
                        m.d_model * m.vocab // 3)


WORK = {
    "tiny": dict(
        kv_row_bytes=256,
        step_flops=[5984256, 57642496, 674369536, 8347648],
        step_bytes=[1585591, 290871, 1805751, 3487159],
        least_s=[1.936008547008547e-06,
                 3.5515384615384615e-07,
                 3.423195614213198e-06,
                 4.257825396825397e-06],
        attn_flops=[512, 512512, 16842752, 559104],
    ),
    "internlm2-2b": dict(
        kv_row_bytes=98304,
        step_flops=[55574003712, 831046090752, 6357316534272, 29843521536],
        step_bytes=[1224803480, 716141720, 1217430680, 1955366040],
        least_s=[0.0014954865445665445,
                 0.004218508074883249,
                 0.03227064230594924,
                 0.002387504322344322],
        attn_flops=[196608, 196804608, 6467616768, 214695936],
    ),
    "phi3-3b": dict(
        kv_row_bytes=393216,
        step_flops=[121497452544, 1980471312384, 15189219999744, 64861765632],
        step_bytes=[3773204000, 1735945760, 3722823200, 6695536160],
        least_s=[0.004607086691086691,
                 0.010053153869969543,
                 0.07710263959260914,
                 0.008175257826617826],
        attn_flops=[393216, 393609216, 12935233536, 429391872],
    ),
}


@pytest.mark.parametrize("cfg", sorted(WORK))
def test_work_counts_are_the_parents(cfg):
    m = _model(cfg)
    a, st, w = m.arch, _steps(), _weights(m)
    pk = work.peaks("TPU v5 lite")
    got = dict(
        kv_row_bytes=a.kv_row_bytes(m),
        step_flops=[a.step_flops(m, s) for s in st],
        step_bytes=[a.step_bytes(m, w, s) for s in st],
        least_s=[work.least_s(m,
                 w,
                 s,
                 pk) for s in st],
        attn_flops=[a.attn_flops(m, c, n)
                    for c, n in ((0, 1), (1000, 1), (0, 256), (77, 13))])
    assert got == WORK[cfg]


READERS = ("icq_matmul_v2_roofline", "icq_dequant_v2_roofline",
           "paged_attention_roofline", "mfu.decode", "mfu.prefill")
KERNELS = ("icq_matmul_v2", "icq_dequant_v2", "paged_attention")
# per kernel: (number of calls, sha256 of the JSON of the call list)
CALLS = {
    "chat": {
        "icq_matmul_v2": (170, "08c467a1616565c0"),
        "icq_dequant_v2": (168, "13d6f991aee1409e"),
        "paged_attention": (24, "fb0cd72f67b16779"),
    },
    "rag": {
        "icq_matmul_v2": (171, "5829bf202541ea40"),
        "icq_dequant_v2": (336, "53c797d7015caa7e"),
        "paged_attention": (24, "772eda1dfe0dca67"),
    },
    "internlm2-2b": {
        "icq_matmul_v2": (340, "bd64a8a475c83c4e"),
        "icq_dequant_v2": (336, "4f5d1bcfb11477e2"),
        "paged_attention": (48, "3f73bbc69e01d03d"),
    },
    "phi3-3b": {
        "icq_matmul_v2": (452, "9cd3552c8ee5216f"),
        "icq_dequant_v2": (448, "e85c064914404be7"),
        "paged_attention": (64, "811c140a6efb82f3"),
    },
}
VALUES = {
    "chat": {
        "icq_matmul_v2_roofline": 0.34195589020651856,
        "icq_dequant_v2_roofline": 4.243027282020745,
        "paged_attention_roofline": 1.9100792288200539,
        "mfu.decode": 0.5776900957626283,
        "mfu.prefill": 0.5776900957626283,
    },
    "rag": {
        "icq_matmul_v2_roofline": 0.3504426116095379,
        "icq_dequant_v2_roofline": 4.247594542484978,
        "paged_attention_roofline": 1.5628500805568648,
        "mfu.decode": 2.114209234810192,
        "mfu.prefill": 2.114209234810192,
    },
}
RECORDED = {"chat": "trace_chat.json", "rag": "trace_rag.json.gz"}


def _calls(w):
    out = {}
    for k, r in zip(KERNELS, READERS):
        cs = [list(c) for s in w.steps for c in _reader(r).calls(w, s)]
        out[k] = (len(cs), _hj(cs))
    return out


@pytest.mark.parametrize("cell", sorted(RECORDED))
def test_reader_calls_and_values_on_recorded_windows_are_the_parents(cell):
    path = os.path.join(HERE, "data", RECORDED[cell])
    with (gzip.open if path.endswith(".gz") else open)(path, "rt") as f:
        d = json.load(f)
    t = tracing.Trace.from_json(d["trace"])
    w = Window(model=_model("internlm2-2b"),
               weights=work.Weights(**d["weights"]),
               peaks=work.peaks("TPU v5 lite"), batch=d["batch"],
               seconds=t.window_s, steps=[work.Step(**s) for s in d["steps"]],
               steps_ok=True, counters=d["counters"], trace=t)
    assert _calls(w) == CALLS[cell]
    assert {n: _reader(n).read(w) for n in READERS} == VALUES[cell]


@pytest.mark.parametrize("cfg", REAL)
def test_reader_calls_at_published_shapes_are_the_parents(cfg):
    m = _model(cfg)
    w = Window(model=m, weights=_weights(m), peaks=None, batch=16,
               seconds=1.0, steps=_steps(), steps_ok=True, counters={},
               trace=None)
    assert _calls(w) == CALLS[cfg]


# the fields of the program's ModelConfig that the block sets or relies on
PROGRAM_CONFIG = {
    "internlm2-2b": dict(
        name='internlm2-2b',
        family='dense',
        n_layers=24,
        d_model=2048,
        n_heads=16,
        n_kv_heads=8,
        head_dim=128,
        d_ff=8192,
        vocab_size=92544,
        attn_type='gqa',
        sliding_window=0,
        rope_theta=1000000.0,
        norm_eps=1e-05,
        param_dtype='bfloat16',
        tie_embeddings=False,
        n_experts=0,
        kv_lora_rank=0,
    ),
    "phi3-3b": dict(
        name='phi3-3b',
        family='dense',
        n_layers=32,
        d_model=3072,
        n_heads=32,
        n_kv_heads=32,
        head_dim=96,
        d_ff=8192,
        vocab_size=32064,
        attn_type='gqa',
        sliding_window=2047,
        rope_theta=10000.0,
        norm_eps=1e-05,
        param_dtype='bfloat16',
        tie_embeddings=False,
        n_experts=0,
        kv_lora_rank=0,
    ),
}


@pytest.mark.parametrize("cfg", REAL)
def test_program_config_is_the_parents(cfg):
    m = _model(cfg)
    c = m.arch.program_config(m)
    assert {k: getattr(c, k) for k in PROGRAM_CONFIG[cfg]} == \
        PROGRAM_CONFIG[cfg]


def test_tree_is_the_parents():
    """The tree the program takes, with each leaf standing in by name."""
    m = _model("tiny")
    t = m.arch.tree({n: n for n, _ in m.arch.LEAVES})
    assert t == dict(
        embed="embed", final_norm="final_norm", lm_head="lm_head",
        stack=dict(ln1="ln1", ln2="ln2",
                   attn=dict(wq="wq", wk="wk", wv="wv", wo="wo"),
                   mlp=dict(w_gate="w_gate", w_up="w_up",
                            w_down="w_down")))
