"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode."""
import functools

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro import core
from repro.core.stats import heavy_tailed_weights
from repro.kernels import backend, ops, ref


@pytest.mark.parametrize("n_bits", [2, 3, 4, 8])
@pytest.mark.parametrize("shape", [(16, 96), (64, 512), (48, 330), (128, 1024)])
def test_dequant_kernel_matches_ref(n_bits, shape):
    R, C = shape
    W = heavy_tailed_weights(R, C, seed=n_bits * 100 + R)
    pk = core.quantize(jnp.asarray(W), n_bits, gamma=0.05)
    rt = ops.to_runtime(pk)
    w_ref = ref.dequant_ref(rt["codes"], rt["bitmap"], rt["codebooks"],
                            n_bits, C)
    # oracle chain: ref equals the core library reconstruction
    np.testing.assert_allclose(
        np.asarray(w_ref), np.asarray(core.dequantize(pk)), rtol=1e-6
    )
    w_k = ops.dequant(rt, block_n=128, block_k=256)
    np.testing.assert_allclose(np.asarray(w_k), np.asarray(w_ref), rtol=1e-6)


@pytest.mark.parametrize("n_bits", [2, 3, 4])
@pytest.mark.parametrize("M", [1, 8, 33])
@pytest.mark.parametrize("x_dtype", [jnp.float32, jnp.bfloat16])
def test_matmul_kernel_matches_ref(n_bits, M, x_dtype):
    R, C = 64, 512
    W = heavy_tailed_weights(R, C, seed=7)
    pk = core.quantize(jnp.asarray(W), n_bits, gamma=0.05)
    rt = ops.to_runtime(pk)
    x = jnp.asarray(
        np.random.default_rng(M).standard_normal((M, C)), x_dtype
    )
    y_ref = ref.matmul_ref(x.astype(jnp.float32), rt["codes"], rt["bitmap"],
                           rt["codebooks"], n_bits, C)
    y_k = ops.matmul(x, rt, block_m=16, block_n=128, block_k=256)
    np.testing.assert_allclose(
        np.asarray(y_k), np.asarray(y_ref), rtol=5e-2 if x_dtype == jnp.bfloat16 else 2e-5,
        atol=5e-2 if x_dtype == jnp.bfloat16 else 2e-5,
    )


@pytest.mark.parametrize("shape", [(4, 100), (20, 700), (64, 2048)])
@pytest.mark.parametrize("C", [4, 16])
def test_kmeans_assign_matches_ref(shape, C):
    R, L = shape
    rng = np.random.default_rng(R * L)
    w = jnp.asarray(rng.standard_normal((R, L)), jnp.float32)
    wt = jnp.asarray(np.abs(rng.standard_normal((R, L))), jnp.float32)
    c = jnp.asarray(np.sort(rng.standard_normal((R, C)), axis=-1), jnp.float32)
    ws_r, vs_r = ref.kmeans_assign_ref(w, wt, c)
    ws_k, vs_k = ops.kmeans_assign(w, wt, c, block_r=16, block_l=256)
    np.testing.assert_allclose(np.asarray(ws_k), np.asarray(ws_r),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(vs_k), np.asarray(vs_r),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("n_bits", [2, 3, 4])
@pytest.mark.parametrize("shape", [(64, 512), (48, 330)])  # aligned + ragged
@pytest.mark.parametrize("M", [1, 8, 300])                 # both dispatch arms
def test_dispatch_parity_fused_vs_dequantize(n_bits, shape, M):
    """backend.linear_apply (pallas arms) ≍ dequantize()-then-matmul.

    M ∈ {1, 8} rides the fused icq_matmul kernel, M = 300 the
    icq_dequant-then-dense-matmul arm; (48, 330) is ragged w.r.t. the
    block lcm for every n_bits."""
    R, C = shape
    W = heavy_tailed_weights(R, C, seed=n_bits * 10 + R)
    pk = core.quantize(jnp.asarray(W), n_bits, gamma=0.05)
    from repro.kernels.platform import decode_m_threshold

    prep = backend.prepare(pk, backend="pallas")
    want_path = "fused" if M <= decode_m_threshold() else "dequant"
    assert backend.choose_path(M, prep) == want_path
    x = jnp.asarray(
        np.random.default_rng(M).standard_normal((M, C)), jnp.float32)
    y_ref = np.asarray(x @ core.dequantize(pk).T)
    y = np.asarray(backend.linear_apply(x, prep))
    np.testing.assert_allclose(y, y_ref, rtol=2e-5, atol=2e-5)


def test_dispatch_xla_arm_bitwise_equals_reference():
    """The pure-XLA arm (CPU default) must reproduce the reference
    dequantize path bit-for-bit (token-parity guarantee for serving)."""
    W = heavy_tailed_weights(48, 330, seed=3)
    pk = core.quantize(jnp.asarray(W), 3, gamma=0.05)
    prep = backend.prepare(pk, backend="xla")
    x = jnp.asarray(
        np.random.default_rng(0).standard_normal((2, 5, 330)), jnp.float32)
    y_ref = np.asarray(x @ core.dequantize(pk).T)
    np.testing.assert_array_equal(
        np.asarray(backend.linear_apply(x, prep)), y_ref)


@pytest.mark.parametrize("n_bits", [2, 3, 4, 8])
@pytest.mark.parametrize("shape", [(16, 96), (48, 330), (64, 512)])
def test_dequant_kernel_v2_matches_ref(n_bits, shape):
    """v2 in-kernel gap->selector decode ≍ the core reconstruction."""
    R, C = shape
    W = heavy_tailed_weights(R, C, seed=n_bits * 100 + R)
    pk = core.quantize(jnp.asarray(W), n_bits, gamma=0.05)
    rt = ops.to_runtime(pk, fmt="v2", tile=256)
    w_k = ops.dequant(rt, block_n=128)
    np.testing.assert_array_equal(
        np.asarray(w_k), np.asarray(core.dequantize(pk)))
    # v2's K block is the checkpoint tile: a block_k request is a
    # caller error, not something to silently ignore
    with pytest.raises(TypeError):
        ops.dequant(rt, block_k=256)


@pytest.mark.parametrize("n_bits", [2, 3, 4])
@pytest.mark.parametrize("M", [1, 8, 33])
def test_matmul_kernel_v2_matches_ref(n_bits, M):
    R, C = 64, 512
    W = heavy_tailed_weights(R, C, seed=7)
    pk = core.quantize(jnp.asarray(W), n_bits, gamma=0.05)
    rt = ops.to_runtime(pk, fmt="v2", tile=256)
    x = jnp.asarray(
        np.random.default_rng(M).standard_normal((M, C)), jnp.float32)
    y_ref = x.astype(jnp.float32) @ core.dequantize(pk).T
    y_k = ops.matmul(x, rt, block_m=16, block_n=128)
    np.testing.assert_allclose(
        np.asarray(y_k), np.asarray(y_ref), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("n_bits", [2, 4])
@pytest.mark.parametrize("M", [1, 300])     # fused arm, dequant arm
def test_v1_v2_bitwise_parity_both_arms(n_bits, M):
    """Acceptance: with identical blocking geometry the v2 stream decode
    must be bit-identical to the v1 bitmap path on BOTH dispatch arms
    (same selector -> same gathered weights -> same f32 accumulation)."""
    R, C = 64, 512                           # aligned: v1/v2 snap equally
    pk = core.quantize(
        jnp.asarray(heavy_tailed_weights(R, C, seed=n_bits)), n_bits,
        gamma=0.05)
    blocks = (16, 32, 256)
    p1 = backend.prepare(pk, backend="pallas", fmt="v1", blocks=blocks)
    p2 = backend.prepare(pk, backend="pallas", fmt="v2", blocks=blocks)
    assert (p1.block_n, p1.block_k) == (p2.block_n, p2.block_k)
    np.testing.assert_array_equal(
        np.asarray(backend.dequantize_prepared(p1)),
        np.asarray(backend.dequantize_prepared(p2)))
    from repro.kernels.platform import decode_m_threshold
    want = "fused" if M <= decode_m_threshold() else "dequant"
    assert backend.choose_path(M, p1) == want
    x = jnp.asarray(
        np.random.default_rng(M).standard_normal((M, C)), jnp.float32)
    np.testing.assert_array_equal(
        np.asarray(backend.linear_apply(x, p1)),
        np.asarray(backend.linear_apply(x, p2)))


@pytest.mark.parametrize("fmt", ["v1", "v2"])
def test_dispatch_xla_arm_bitwise_equals_reference_both_fmts(fmt):
    """The pure-XLA arm must reproduce the reference dequantize path
    bit-for-bit in either runtime format (token-parity guarantee): the
    v2 checkpoint decode yields the exact selector the stream encodes."""
    W = heavy_tailed_weights(48, 330, seed=3)
    pk = core.quantize(jnp.asarray(W), 3, gamma=0.05)
    prep = backend.prepare(pk, backend="xla", fmt=fmt)
    assert prep.fmt == fmt
    x = jnp.asarray(
        np.random.default_rng(0).standard_normal((2, 5, 330)), jnp.float32)
    y_ref = np.asarray(x @ core.dequantize(pk).T)
    np.testing.assert_array_equal(
        np.asarray(backend.linear_apply(x, prep)), y_ref)


@pytest.mark.parametrize("n_bits", [2, 3])
@pytest.mark.parametrize("M", [1, 300])
def test_dispatch_parity_v2_ragged(n_bits, M):
    """v2 pallas arms on a ragged shape (block lcm does not divide d_in)
    still match the reference to f32-accumulation tolerance."""
    R, C = 48, 330
    pk = core.quantize(
        jnp.asarray(heavy_tailed_weights(R, C, seed=n_bits * 10 + R)),
        n_bits, gamma=0.05)
    prep = backend.prepare(pk, backend="pallas", fmt="v2")
    x = jnp.asarray(
        np.random.default_rng(M).standard_normal((M, C)), jnp.float32)
    y_ref = np.asarray(x @ core.dequantize(pk).T)
    np.testing.assert_allclose(
        np.asarray(backend.linear_apply(x, prep)), y_ref,
        rtol=2e-5, atol=2e-5)


_DEEP_ROWS, _DEEP_D_IN, _DEEP_TILE = 44, 4096, 512    # ragged rows, T = 8


@functools.lru_cache(maxsize=None)
def _deep_stream_runtime(b: int):
    """A 2-bit v2 runtime dict over a (44, 4096) weight at forced b, with
    two hand-built rows: row 0 has outliers at 0, 511, 512 and 4095
    (both edges of a tile, the last position); row 1 holds every outlier
    in the first three tiles, so tiles 3-7 have none."""
    W = heavy_tailed_weights(_DEEP_ROWS, _DEEP_D_IN, seed=b)
    big = 1.5 * float(np.abs(W).max())          # above every other |w|
    W[0, [0, 511, 512, 4095]] = [big, -big, big, -big]
    p = core.num_outliers(_DEEP_D_IN, 0.05)
    early = np.random.default_rng(b).choice(3 * _DEEP_TILE, p, replace=False)
    W[1, early] = big
    pk = core.quantize(jnp.asarray(W), 2, gamma=0.05, b=b)
    rt = ops.to_runtime(pk, fmt="v2", tile=_DEEP_TILE)
    return pk, rt


@pytest.mark.parametrize("arm", ["dequant", "matmul"])
@pytest.mark.parametrize("b", [2, 3, 6, 11])     # 16 / 10 / 5 / 2 a word
def test_v2_selector_plane_deep_stream(b, arm):
    """The v2 selector plane over 8 checkpoint tiles: small b forces
    escapes, late tiles read symbols deep in the stream, and the rows
    past 44 (padding up to 128 lanes) have empty streams. The dequant
    arm equals the core reconstruction bit for bit."""
    pk, rt = _deep_stream_runtime(b)
    assert pk.b == b and rt["offs"].shape == (_DEEP_ROWS, 9)
    mask = np.asarray(core.decode_to_dense_mask(pk.stream))
    assert mask[0, [0, 511, 512, 4095]].all()
    assert not mask[1, 3 * _DEEP_TILE:].any()
    w_ref = np.asarray(core.dequantize(pk))
    if arm == "dequant":
        np.testing.assert_array_equal(
            np.asarray(ops.dequant(rt, block_n=128)), w_ref)
    else:
        x = jnp.asarray(np.random.default_rng(b).standard_normal(
            (8, _DEEP_D_IN)), jnp.float32)
        np.testing.assert_allclose(
            np.asarray(ops.matmul(x, rt, block_m=8, block_n=128)),
            np.asarray(x @ w_ref.T), rtol=2e-5, atol=2e-5)


def test_runtime_format_bits():
    """Runtime overlay = n + 1 + codebooks bits; storage = n + ~0.31."""
    W = heavy_tailed_weights(256, 4096, seed=9)
    pk = core.quantize(jnp.asarray(W), 2, gamma=0.05)
    rt = ops.to_runtime(pk)
    rt_bits = ops.runtime_bits_per_weight(rt)
    st_bits = pk.bits_per_weight()["total"]
    assert st_bits < rt_bits < st_bits + 0.85   # bitmap costs ~0.7 extra
    assert rt_bits < 16 / 4                     # still ~4x under bf16


# ---------------------------------------------------------------------------
# bf16 accumulator option (ICQ_ACCUM_DTYPE)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("fmt", ["v1", "v2"])
@pytest.mark.parametrize("n_bits", [2, 4])
def test_accum_bf16_parity_tolerance_both_formats(fmt, n_bits):
    """accum='bf16' halves the fused matmul's VMEM accumulator scratch;
    partial sums round to bf16 at every K step, so the result must agree
    with the f32 accumulator to bf16 mantissa tolerance — and the f32
    accumulator path must stay bitwise what it was (the default)."""
    R, C = 48, 512
    W = heavy_tailed_weights(R, C, seed=n_bits * 7)
    pk = core.quantize(jnp.asarray(W), n_bits, gamma=0.05)
    rt = ops.to_runtime(pk, fmt=fmt, **(dict(tile=256) if fmt == "v2" else {}))

    x = jnp.asarray(
        np.random.default_rng(5).standard_normal((8, C)), jnp.float32)
    mkw = dict(block_m=8, block_n=16)
    if fmt == "v1":
        mkw["block_k"] = 256
    y32 = np.asarray(ops.matmul(x, rt, accum="f32", **mkw))
    ydef = np.asarray(ops.matmul(x, rt, **mkw))
    np.testing.assert_array_equal(ydef, y32)    # f32 is the default
    ybf = np.asarray(ops.matmul(x, rt, accum="bf16", **mkw))
    np.testing.assert_allclose(ybf, y32, rtol=2e-2, atol=2e-2)
    assert not np.array_equal(ybf, y32)         # bf16 rounding is real


def test_accum_env_default_vmem_estimate_and_keys(monkeypatch):
    from repro.kernels import autotune
    from repro.kernels.platform import default_accum_dtype

    monkeypatch.delenv("ICQ_ACCUM_DTYPE", raising=False)
    assert default_accum_dtype() == "f32"
    monkeypatch.setenv("ICQ_ACCUM_DTYPE", "bf16")
    assert default_accum_dtype() == "bf16"
    monkeypatch.setenv("ICQ_ACCUM_DTYPE", "fp8")
    with pytest.raises(ValueError):
        default_accum_dtype()
    monkeypatch.delenv("ICQ_ACCUM_DTYPE", raising=False)

    # the bf16 accumulator shaves the acc-scratch VMEM term
    e32 = backend.vmem_bytes_estimate(128, 128, 512, n_bits=3, C=16,
                                      accum="f32")
    ebf = backend.vmem_bytes_estimate(128, 128, 512, n_bits=3, C=16,
                                      accum="bf16")
    assert ebf == e32 - 128 * 128 * 2

    # accumulator width is part of the autotune key (block winners tuned
    # under bf16 must not be replayed by f32 runs); f32 keeps the
    # un-suffixed spelling so existing cache files stay valid
    k_f32 = autotune.matmul_key(1, 16, 96, 4, "pallas", True)
    k_bf16 = autotune.matmul_key(1, 16, 96, 4, "pallas", True,
                                 accum="bf16")
    assert k_f32 != k_bf16 and k_bf16.endswith("_acc-bf16")
    assert "acc-" not in k_f32
    monkeypatch.setenv("ICQ_ACCUM_DTYPE", "bf16")
    assert autotune.matmul_key(1, 16, 96, 4, "pallas", True) == k_bf16
    monkeypatch.delenv("ICQ_ACCUM_DTYPE", raising=False)

    W = heavy_tailed_weights(16, 96, seed=0)
    pk = core.quantize(jnp.asarray(W), 4, gamma=0.05)
    rt = ops.to_runtime(pk)
    with pytest.raises(ValueError, match="accum"):
        ops.matmul(jnp.zeros((2, 96), jnp.float32), rt, accum="f16")
