"""Kernel-backed execution layer: prepare, dispatch, autotune, serving.

(Names mention "kernel" so ``pytest -k kernel`` smoke-sweeps this file
together with tests/test_kernels.py.)
"""
import json

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro import core
from repro.core.icquant import to_runtime_format
from repro.core.stats import heavy_tailed_weights
from repro.kernels import autotune, backend, ops
from repro.kernels.platform import (
    decode_m_threshold,
    default_backend,
    default_interpret,
)
from repro.launch.quantize import quantize_tree
from repro.launch.steps import prepare_serving_params
from repro.models.linear import as_dense, linear, weight_shape


def _pack(R=48, C=330, n_bits=3, seed=1):
    W = heavy_tailed_weights(R, C, seed=seed)
    return core.quantize(jnp.asarray(W), n_bits, gamma=0.05)


# ---------------------------------------------------------------------------
# satellite regressions
# ---------------------------------------------------------------------------

def test_kernel_weight_shape_all_representations():
    """Regression: weight_shape(ICQRuntime) used to fall through to
    w.shape and raise AttributeError."""
    pk = _pack()
    rt = to_runtime_format(pk)
    prep = backend.prepare(pk)
    assert weight_shape(pk) == (330, 48)
    assert weight_shape(rt) == (330, 48)          # <- the old crash
    assert weight_shape(prep) == (330, 48)
    assert weight_shape(jnp.zeros((7, 9))) == (7, 9)


def test_kernel_runtime_bits_counts_f32_codebooks():
    """runtime_bits_per_weight must charge codebooks at their stored f32
    width: total ≈ n (codes) + 1 (bitmap) + 32·2^(n+1)/d_in (codebooks),
    exactly when d_in divides the packing words."""
    d_out, d_in = 64, 4096
    for n_bits in (2, 4):
        pk = _pack(d_out, d_in, n_bits, seed=n_bits)
        rt = ops.to_runtime(pk)
        assert rt["codebooks"].dtype == jnp.float32
        got = ops.runtime_bits_per_weight(rt)
        want = n_bits + 1 + 32 * (2 << n_bits) / d_in
        assert got == pytest.approx(want, rel=1e-6), (n_bits, got, want)


def test_kernel_interpret_default_platform_and_env(monkeypatch):
    monkeypatch.delenv("ICQ_INTERPRET", raising=False)
    monkeypatch.delenv("ICQ_BACKEND", raising=False)
    monkeypatch.setenv("ICQ_PLATFORM", "tpu")
    assert default_interpret() is False
    assert default_backend() == "pallas"
    monkeypatch.setenv("ICQ_PLATFORM", "cpu")
    assert default_interpret() is True
    assert default_backend() == "xla"
    monkeypatch.setenv("ICQ_INTERPRET", "0")
    assert default_interpret() is False
    monkeypatch.setenv("ICQ_BACKEND", "pallas")
    assert default_backend() == "pallas"


# ---------------------------------------------------------------------------
# prepared layout
# ---------------------------------------------------------------------------

def test_kernel_prepared_layout_blocked_and_padded():
    pk = _pack(48, 330, 3)

    prep = backend.prepare(pk, backend="pallas", fmt="v1")
    assert prep.fmt == "v1" and prep.syms is None
    # transposed bit planes: lanes are rows, n planes of pk/32 words
    assert prep.codes.shape == (3 * prep.pk // 32, prep.pn)
    assert prep.bitmap.shape == (prep.pk // 32, prep.pn)
    assert prep.codebooks.shape == (16, prep.pn)
    assert prep.pn % prep.block_n == 0 and prep.pk % prep.block_k == 0
    assert prep.pk % 256 == 0 and prep.pk >= prep.d_in
    assert prep.pn >= prep.d_out
    assert backend.blocks_fit(prep.block_n, prep.block_k, prep.pn, prep.pk)
    # padding accounted in the HBM bits (and still far under bf16)
    assert prep.bits_per_weight() < 16

    prep2 = backend.prepare(pk, backend="pallas", fmt="v2")
    assert prep2.fmt == "v2" and prep2.bitmap is None
    assert prep2.b == pk.b
    assert prep2.pk % prep2.block_k == 0
    # checkpoint sidecar blocked to block_k: one offset per tile + sentinel
    T = prep2.pk // prep2.block_k
    assert prep2.offs.shape == (T + 1, prep2.pn)
    assert prep2.dbase.shape == (T, prep2.pn)
    assert prep2.offs.dtype == jnp.uint16
    assert prep2.dbase.dtype == jnp.uint8          # b = 6 <= 8
    # v2 serves cheaper than the dense bitmap for the same weight
    assert prep2.bits_per_weight() < prep.bits_per_weight()


def test_kernel_prepare_accepts_runtime_and_dict():
    pk = _pack()
    w_ref = np.asarray(core.dequantize(pk))
    for src in (to_runtime_format(pk), ops.to_runtime(pk)):
        prep = backend.prepare(src)
        np.testing.assert_array_equal(
            np.asarray(backend.dequantize_prepared(prep)), w_ref)


def test_kernel_prepare_tree_and_dense_cache_modes():
    leaf = jnp.asarray(heavy_tailed_weights(96, 64, seed=5)).T  # (64, 96)
    params = dict(a=dict(w=leaf), ln=jnp.ones((4,)))
    qparams, _ = quantize_tree(params, 4)
    prepped = prepare_serving_params(qparams, mode="prepared")
    assert isinstance(prepped["a"]["w"], backend.ICQPrepared)
    assert prepped["ln"] is qparams["ln"]
    dense = prepare_serving_params(qparams, mode="dense")
    assert dense["a"]["w"].shape == leaf.shape      # (d_in, d_out) restored
    np.testing.assert_array_equal(
        np.asarray(dense["a"]["w"]),
        np.asarray(as_dense(qparams["a"]["w"])))
    assert prepare_serving_params(qparams, mode="none") is qparams
    with pytest.raises(ValueError):
        prepare_serving_params(qparams, mode="bogus")


def test_kernel_prepared_slices_under_scan_like_indexing():
    """Layer-stacked prepared weights must survive the scan leaf slicing
    stack_apply performs (children lose the lead axis, statics persist)."""
    stacked = jnp.stack([
        jnp.asarray(heavy_tailed_weights(40, 64, seed=s)).T for s in (1, 2)
    ])                                               # (2, 64, 40) leaf
    qp, _ = quantize_tree(dict(w=stacked), 4)
    prep = backend.prepare_tree(qp)["w"]
    assert prep.codes.ndim == 3
    layer0 = jax.tree.map(lambda a: a[0], prep)
    assert isinstance(layer0, backend.ICQPrepared)
    assert layer0.codes.ndim == 2
    w0 = np.asarray(backend.dequantize_prepared(layer0))
    w_ref = np.asarray(core.dequantize(qp["w"]))[0]
    np.testing.assert_array_equal(w0, w_ref)


def test_kernel_moe_stacked_prepared_dequant_matches_reference():
    stacked = jnp.stack([
        jnp.asarray(heavy_tailed_weights(48, 96, seed=s)).T for s in range(3)
    ])                                               # (3, 96, 48)
    qp, _ = quantize_tree(dict(w=stacked), 3)
    w_ref = np.asarray(core.dequantize(qp["w"]))     # (3, 48, 96)
    for be in ("xla", "pallas"):
        prep = backend.prepare(qp["w"], backend=be)
        got = np.asarray(backend.dequantize_prepared(prep))
        np.testing.assert_allclose(got, w_ref, rtol=1e-6)

    from repro.models.moe import _expert_weight
    ew = _expert_weight(backend.prepare(qp["w"]), jnp.float32)
    assert ew.shape == (3, 96, 48)
    np.testing.assert_allclose(
        np.asarray(ew), np.swapaxes(w_ref, -1, -2), rtol=1e-6)


def test_kernel_dispatch_threshold_env(monkeypatch):
    pk = _pack()
    prep = backend.prepare(pk, backend="pallas")
    assert backend.choose_path(decode_m_threshold(), prep) == "fused"
    assert backend.choose_path(decode_m_threshold() + 1, prep) == "dequant"
    monkeypatch.setenv("ICQ_DECODE_M", "4")
    assert backend.choose_path(8, prep) == "dequant"
    # xla backend always takes the xla arm
    assert backend.choose_path(1, backend.prepare(pk, backend="xla")) == "xla"


# ---------------------------------------------------------------------------
# autotuner
# ---------------------------------------------------------------------------

def test_kernel_autotune_cache_roundtrip(tmp_path, monkeypatch):
    cache = tmp_path / "tune.json"
    monkeypatch.setenv("ICQ_AUTOTUNE_CACHE", str(cache))
    autotune.reset()
    got = autotune.autotune_matmul(
        1, 16, 96, 4, interpret=True,
        candidates=[(8, 16, 96), (8, 8, 96)], iters=1)
    # both candidates snap to the same legal blocking: all 16 rows in one
    # N block, one 256-row K group; the resolved blocking is recorded
    assert not got["cached"] and got["blocks"] == (8, 16, 256)
    assert cache.exists()
    key = autotune.matmul_key(1, 16, 96, 4, "pallas", True)
    assert json.loads(cache.read_text())[key] == list(got["blocks"])
    # second call: in-memory hit
    again = autotune.autotune_matmul(1, 16, 96, 4, interpret=True)
    assert again["cached"] and again["blocks"] == got["blocks"]
    # fresh process simulation: disk hit
    autotune.reset()
    assert autotune.lookup(key) == list(got["blocks"])
    autotune.reset()


def test_kernel_prepare_consults_autotune_cache(tmp_path, monkeypatch):
    monkeypatch.setenv("ICQ_AUTOTUNE_CACHE", str(tmp_path / "tune.json"))
    autotune.reset()
    pk = _pack(48, 330, 3)
    key = autotune.matmul_key(1, 48, 330, 3, "pallas", default_interpret())
    # padded d_in = 512 (two 256-row plane groups): block_k=512 survives
    # the snap unchanged; 48 rows fit one N block, so block_n covers them
    autotune.record(key, [64, 32, 512])
    prep = backend.prepare(pk, backend="pallas", fmt="v1")
    assert (prep.block_m, prep.block_n, prep.block_k) == (64, 48, 512)
    # a cached block_k that does not divide the padded width snaps down
    autotune.record(key, [64, 32, 384])
    prep2 = backend.prepare(pk, backend="pallas", fmt="v1")
    assert prep2.block_k == 256 and prep2.pk == 512

    # v2 tunes under its own key; its block_k becomes the checkpoint tile
    key2 = autotune.matmul_key(1, 48, 330, 3, "pallas", default_interpret(),
                               fmt="v2")
    assert key2 != key and key2.endswith("_v2")
    autotune.record(key2, [64, 32, 256])
    prep3 = backend.prepare(pk, backend="pallas", fmt="v2")
    assert prep3.block_k == 256 and prep3.offs.shape[0] == 512 // 256 + 1
    autotune.reset()


def test_kernel_autotune_corrupted_cache_falls_back(tmp_path, monkeypatch):
    """A corrupted / partial cache file must mean 'sweep', never a crash."""
    cache = tmp_path / "tune.json"
    monkeypatch.setenv("ICQ_AUTOTUNE_CACHE", str(cache))
    for garbage in ('{"matmul/m1_o16_i96_n4_pallas-int": [8, 16', "not json",
                    ""):
        cache.write_text(garbage)
        autotune.reset()
        assert autotune.lookup("matmul/m1_o16_i96_n4_pallas-int") is None
        got = autotune.autotune_matmul(
            1, 16, 96, 4, interpret=True,
            candidates=[(8, 16, 96)], iters=1)
        assert not got["cached"] and got["blocks"] == (8, 16, 256)
        # the sweep rewrote a valid cache file over the garbage
        assert json.loads(cache.read_text())
    autotune.reset()


def test_kernel_autotune_v2_sweep_and_key(tmp_path, monkeypatch):
    monkeypatch.setenv("ICQ_AUTOTUNE_CACHE", str(tmp_path / "tune.json"))
    autotune.reset()
    got = autotune.autotune_matmul(
        1, 16, 96, 4, interpret=True, fmt="v2",
        candidates=[(8, 16, 96), (8, 8, 96)], iters=1)
    assert not got["cached"]
    key = autotune.matmul_key(1, 16, 96, 4, "pallas", True, fmt="v2")
    assert autotune.lookup(key) == list(got["blocks"])
    # the v1 spelling of the same shape is a distinct cache entry
    assert autotune.lookup(
        autotune.matmul_key(1, 16, 96, 4, "pallas", True)) is None
    autotune.reset()


# ---------------------------------------------------------------------------
# v2 checkpointed-stream runtime format
# ---------------------------------------------------------------------------

def test_kernel_runtime_fmt_env_override(monkeypatch):
    from repro.kernels.platform import default_runtime_fmt

    monkeypatch.delenv("ICQ_RUNTIME_FMT", raising=False)
    assert default_runtime_fmt() == "v2"
    pk = _pack()
    assert backend.prepare(pk).fmt == "v2"
    monkeypatch.setenv("ICQ_RUNTIME_FMT", "v1")
    assert default_runtime_fmt() == "v1"
    assert backend.prepare(pk).fmt == "v1"
    monkeypatch.setenv("ICQ_RUNTIME_FMT", "v3")
    with pytest.raises(ValueError):
        default_runtime_fmt()


def test_kernel_prepare_v2_falls_back_for_bitmap_sources():
    """ICQRuntime / v1 dicts carry no gap stream: prepare(fmt='v2') keeps
    serving them as v1 instead of failing."""
    pk = _pack()
    for src in (to_runtime_format(pk), ops.to_runtime(pk, fmt="v1")):
        prep = backend.prepare(src, fmt="v2")
        assert prep.fmt == "v1" and prep.bitmap is not None


def test_kernel_prepare_accepts_v2_dict():
    pk = _pack()
    rt = ops.to_runtime(pk, fmt="v2", tile=128)
    prep = backend.prepare(rt)
    assert prep.fmt == "v2"
    assert prep.block_k == rt["tile"]       # checkpoint tile is binding
    np.testing.assert_array_equal(
        np.asarray(backend.dequantize_prepared(prep)),
        np.asarray(core.dequantize(pk)))
    with pytest.raises(ValueError):
        backend.prepare(rt, fmt="v1")       # bitmap never materialized


def test_kernel_codebook_dtype_bf16():
    """Satellite: bf16 codebook option halves the codebook HBM charge;
    dequant error stays within bf16 rounding of the f32 levels."""
    pk = _pack(64, 512, 4)
    w32 = np.asarray(core.dequantize(pk))
    for fmt in ("v1", "v2"):
        p32 = backend.prepare(pk, fmt=fmt, codebook_dtype="f32")
        p16 = backend.prepare(pk, fmt=fmt, codebook_dtype="bf16")
        assert p16.codebooks.dtype == jnp.bfloat16
        cb_elems = p32.codebooks.size
        want_saving = cb_elems * 16 / (64 * 512)
        got_saving = p32.bits_per_weight() - p16.bits_per_weight()
        assert got_saving == pytest.approx(want_saving, rel=1e-6)
        w16 = np.asarray(backend.dequantize_prepared(p16), np.float32)
        np.testing.assert_allclose(w16, w32, rtol=8e-3, atol=8e-3)
    with pytest.raises(ValueError):
        backend.prepare(pk, codebook_dtype="f64")


def test_kernel_vmem_budget_clamps_blocks(monkeypatch):
    """Satellite: block candidates whose estimated working set busts the
    VMEM budget are clamped in prepare() before any compiler sees them
    (n_bits=8: eight bit planes and 512 codebook levels per row)."""
    pk = _pack(64, 512, 8)
    prep = backend.prepare(pk, backend="pallas", fmt="v1")
    C = prep.codebooks.shape[-2]                     # (C, pn) layout
    assert C == 512
    est = backend.vmem_bytes_estimate(
        prep.block_m, prep.block_n, prep.block_k, n_bits=8, C=C, fmt="v1")
    assert est <= backend.vmem_budget_bytes()
    # a tighter explicit budget clamps K, then M
    monkeypatch.setenv("ICQ_VMEM_BUDGET_MB", "1")
    tight = backend.prepare(pk, backend="pallas", fmt="v1")
    assert (tight.block_m, tight.block_k) < (prep.block_m, prep.block_k)
    est2 = backend.vmem_bytes_estimate(
        tight.block_m, tight.block_n, tight.block_k, n_bits=8, C=C, fmt="v1")
    assert est2 <= 2**20
    # parity survives clamping
    x = jnp.asarray(
        np.random.default_rng(1).standard_normal((3, 512)), jnp.float32)
    np.testing.assert_allclose(
        np.asarray(backend.linear_apply(x, tight)),
        np.asarray(x @ core.dequantize(pk).T), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("n_bits", [2, 3, 4])
def test_kernel_v2_outlier_overhead_bench_configs(n_bits):
    """Acceptance: on the bench geometry the v2 runtime pays <= 0.45 b/w
    for outlier selection (stream + checkpoints + padding) where the v1
    bitmap pays ~1.0 — measured by runtime_bits_per_weight accounting."""
    pk = _pack(512, 2048, n_bits, seed=n_bits)
    rt1 = ops.to_runtime(pk, fmt="v1")
    rt2 = ops.to_runtime(pk, fmt="v2")
    over1 = ops.runtime_outlier_bits_per_weight(rt1)
    over2 = ops.runtime_outlier_bits_per_weight(rt2)
    assert over1 >= 1.0                       # dense 1-bit selector
    assert over2 <= 0.45, (n_bits, over2)     # checkpointed stream
    # total runtime bits drop by the same margin
    assert ops.runtime_bits_per_weight(rt1) - ops.runtime_bits_per_weight(
        rt2) == pytest.approx(over1 - over2, rel=1e-6)
    # and stay within ~0.15 b/w of the storage stream itself
    assert over2 <= pk.bits_per_weight()["index"] + 0.15


def test_kernel_runtime_bits_itemsize_derived():
    """Satellite: accounting derives widths from itemsize — the uint16
    offsets and uint8 deltas of the v2 sidecar bill at 16/8 bits, not a
    hardcoded 32."""
    pk = _pack(64, 512, 4)
    rt = ops.to_runtime(pk, fmt="v2")
    total_w = 64 * 512
    want = (
        rt["codes"].size * 32 + rt["syms"].size * 32
        + rt["offs"].size * 16 + rt["dbase"].size * 8
        + rt["codebooks"].size * 32
    ) / total_w
    assert ops.runtime_bits_per_weight(rt) == pytest.approx(want, rel=1e-9)
    rt16 = ops.to_runtime(pk, fmt="v2", codebook_dtype="bf16")
    assert ops.runtime_bits_per_weight(rt) - ops.runtime_bits_per_weight(
        rt16) == pytest.approx(rt["codebooks"].size * 16 / total_w, rel=1e-6)


# ---------------------------------------------------------------------------
# serving engine routes through the dispatch layer
# ---------------------------------------------------------------------------

def test_kernel_engine_prepared_token_parity():
    """GenerationEngine decode with ICQ weights goes through the prepared
    dispatch layer (no full dequantize() in the per-step hot path) and
    generates IDENTICAL tokens to the reference in-graph-decode path —
    for both the v1 bitmap and the v2 checkpointed-stream formats."""
    from repro.configs import get_config, smoke_variant
    from repro.models import init_model
    from repro.serving import GenerationEngine, Request

    cfg = smoke_variant(get_config("llama3.2-1b"))
    params = init_model(jax.random.PRNGKey(0), cfg)
    qparams, _ = quantize_tree(params, 4, gamma=0.05)
    prompt = np.random.default_rng(2).integers(
        0, cfg.vocab_size, 5).astype(np.int32)

    e_ref = GenerationEngine(qparams, cfg, batch_size=1, max_len=24,
                             weight_cache="none")
    e_ref.submit(Request(0, prompt, max_new_tokens=4))
    ref_tokens = e_ref.run()[0].generated

    for fmt in ("v1", "v2"):
        e_prep = GenerationEngine(qparams, cfg, batch_size=1, max_len=24,
                                  runtime_fmt=fmt)
        leaves = [
            w for w in jax.tree.leaves(
                e_prep.params,
                is_leaf=lambda x: isinstance(x, backend.ICQPrepared))
            if isinstance(w, backend.ICQPrepared)
        ]
        assert leaves, "engine did not prepare ICQ weights"
        assert all(w.fmt == fmt for w in leaves)
        e_prep.submit(Request(0, prompt, max_new_tokens=4))
        assert e_prep.run()[0].generated == ref_tokens, fmt


# ---------------------------------------------------------------------------
# batched-M autotune entries + per-arm block tables (ISSUE-3 satellite)
# ---------------------------------------------------------------------------

def test_kernel_bucket_m_largest_not_exceeding():
    assert backend.bucket_m(1) == 1
    assert backend.bucket_m(8) == 1       # decode batches reuse the M=1 key
    assert backend.bucket_m(63) == 1
    assert backend.bucket_m(64) == 64
    assert backend.bucket_m(255) == 64
    assert backend.bucket_m(256) == 256
    assert backend.bucket_m(4096) == 256  # saturates at the largest bucket


def test_kernel_arm_blocks_consults_per_arm_winners(tmp_path, monkeypatch):
    monkeypatch.setenv("ICQ_AUTOTUNE_CACHE", str(tmp_path / "tune.json"))
    autotune.reset()
    pk = _pack()
    prep = backend.prepare(pk, backend="pallas", interpret=True)
    pn, pk_cols = prep.pn, prep.pk

    # no cache entries: every arm falls back to the prepare-time table
    base = (prep.block_m, prep.block_n, prep.block_k)
    assert backend.arm_blocks(1, prep) == base
    assert backend.arm_blocks(200, prep) == base

    # fused arm: decode (M=1) and prefill (M=64 bucket) key independently
    autotune.record(autotune.matmul_key(
        1, prep.d_out, prep.d_in, prep.n_bits, "pallas", True,
        fmt=prep.fmt), (8, pn, prep.block_k))
    assert backend.arm_blocks(1, prep) == (8, pn, prep.block_k)

    # dequant arm (M past the decode threshold) uses the M-free dequant key
    autotune.record(autotune.dequant_key(
        prep.d_out, prep.d_in, prep.n_bits, "pallas", True,
        fmt=prep.fmt), (pn, prep.block_k))
    bm, bn, bk = backend.arm_blocks(200, prep)
    assert (bn, bk) == (pn, prep.block_k)

    # a winner that does not tile the prepared padding is rejected
    autotune.record(autotune.matmul_key(
        1, prep.d_out, prep.d_in, prep.n_bits, "pallas", True,
        fmt=prep.fmt), (8, pn + 8, pk_cols + 64))
    assert backend.arm_blocks(1, prep) == base
    autotune.reset()


def test_kernel_arm_blocks_v2_pins_checkpoint_tile(tmp_path, monkeypatch):
    """v2 block_k is baked into the checkpoint sidecar: an arm winner may
    re-block M/N but its K tile must be overridden to the prepared one."""
    monkeypatch.setenv("ICQ_AUTOTUNE_CACHE", str(tmp_path / "tune.json"))
    autotune.reset()
    pk = _pack()
    prep = backend.prepare(pk, backend="pallas", interpret=True, fmt="v2")
    assert prep.fmt == "v2"
    pn = prep.pn
    autotune.record(autotune.matmul_key(
        1, prep.d_out, prep.d_in, prep.n_bits, "pallas", True,
        fmt="v2"), (16, pn, 99999))
    assert backend.arm_blocks(1, prep) == (16, pn, prep.block_k)
    autotune.reset()


def test_kernel_autotune_arms_populates_all_keys(tmp_path, monkeypatch):
    monkeypatch.setenv("ICQ_AUTOTUNE_CACHE", str(tmp_path / "tune.json"))
    autotune.reset()
    table = autotune.autotune_arms(16, 96, 4, interpret=True, iters=1,
                                   prefill_ms=(64,))
    assert autotune.lookup(
        autotune.matmul_key(1, 16, 96, 4, "pallas", True)) is not None
    assert autotune.lookup(
        autotune.matmul_key(64, 16, 96, 4, "pallas", True)) is not None
    assert autotune.lookup(
        autotune.dequant_key(16, 96, 4, "pallas", True)) is not None
    assert set(table) == {"decode", "prefill", "dequant"}
    assert list(table["prefill"]) == [64]
    autotune.reset()


# ---------------------------------------------------------------------------
# XLA-arm decoded-selector memo (ISSUE-5 satellite)
# ---------------------------------------------------------------------------

def test_kernel_xla_sel_memo_built_only_for_xla_v2(monkeypatch):
    monkeypatch.delenv("ICQ_XLA_SEL_MEMO", raising=False)
    pk = _pack()
    assert backend.prepare(pk, backend="xla", fmt="v2").sel_memo is not None
    assert backend.prepare(pk, backend="xla", fmt="v1").sel_memo is None
    assert backend.prepare(pk, backend="pallas", fmt="v2").sel_memo is None
    monkeypatch.setenv("ICQ_XLA_SEL_MEMO", "0")
    assert backend.prepare(pk, backend="xla", fmt="v2").sel_memo is None


def test_kernel_xla_sel_memo_bitwise_parity(monkeypatch):
    """The memo replaces the per-call in-graph gap-stream decode: outputs
    must be bit-identical with and without it (and to the v1 bitmap)."""
    pk = _pack()
    x = jnp.asarray(
        np.random.default_rng(0).normal(size=(3, 330)).astype(np.float32))
    monkeypatch.setenv("ICQ_XLA_SEL_MEMO", "0")
    p_plain = backend.prepare(pk, backend="xla", fmt="v2")
    monkeypatch.setenv("ICQ_XLA_SEL_MEMO", "1")
    p_memo = backend.prepare(pk, backend="xla", fmt="v2")
    assert p_memo.sel_memo is not None and p_plain.sel_memo is None
    y_plain = np.asarray(backend.linear_apply(x, p_plain))
    y_memo = np.asarray(backend.linear_apply(x, p_memo))
    assert np.array_equal(y_plain.view(np.uint8), y_memo.view(np.uint8))
    w_plain = np.asarray(backend.dequantize_prepared(p_plain))
    w_memo = np.asarray(backend.dequantize_prepared(p_memo))
    assert np.array_equal(w_plain.view(np.uint8), w_memo.view(np.uint8))


def test_kernel_xla_sel_memo_excluded_from_bits_accounting(monkeypatch):
    """The memo is an off-TPU fallback compute cache, not part of the
    runtime format: the v2 bits/weight story must not change with it."""
    pk = _pack()
    monkeypatch.setenv("ICQ_XLA_SEL_MEMO", "0")
    p_plain = backend.prepare(pk, backend="xla", fmt="v2")
    monkeypatch.setenv("ICQ_XLA_SEL_MEMO", "1")
    p_memo = backend.prepare(pk, backend="xla", fmt="v2")
    assert p_memo.bits_per_weight() == p_plain.bits_per_weight()
    assert (p_memo.outlier_bits_per_weight()
            == p_plain.outlier_bits_per_weight())


def test_kernel_xla_sel_memo_slices_under_stacked_lead_axes():
    """Stacked (layer-scanned) prepared weights slice the memo child with
    the other children; the sliced layer must still decode bitwise."""
    pk = _pack()
    # fake a 2-layer stack by stacking the packed children
    stacked = jax.tree.map(lambda a: jnp.stack([a, a]), pk)
    prep = backend.prepare(stacked, backend="xla", fmt="v2")
    assert prep.sel_memo is not None and prep.sel_memo.ndim == 3
    layer0 = jax.tree.map(lambda a: a[0], prep)
    flat = backend.prepare(pk, backend="xla", fmt="v2")
    a = np.asarray(backend.dequantize_prepared(layer0))
    b = np.asarray(backend.dequantize_prepared(flat))
    assert np.array_equal(a.view(np.uint8), b.view(np.uint8))
