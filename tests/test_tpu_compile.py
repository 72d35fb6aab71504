"""Compile the main-path Pallas kernels for a TPU v5e at real widths.

Nothing runs: the installed TPU compiler lowers and compiles each kernel
for a v5e chip that is described, not attached, so a block layout or a
kernel body the chip's compiler would refuse fails here at no chip time.
Interpret-mode tests cannot see either. Widths are InternLM2-1.8B's
(d_model 2048, d_ff 8192, 8 KV heads of 128 with 2 query heads each),
Phi-3-mini's ICQ projections (d_model 3072, d_ff 8192) and MiniCPM3-4B's
latent attention (40 heads over a 256-wide latent with
a 32-wide rope side channel). The ICQ weights are real ``prepare()``
outputs of seeded synthetic weights, so the compiled shapes are the ones
the serving path builds.

The topology is described inside a module fixture, never at import, so
that only the pytest worker running this file loads the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import autotune, backend
from repro.kernels.paged_attention import paged_attention

# InternLM2-1.8B projections as (d_out, d_in)
WIDTHS = [(2048, 2048), (8192, 2048), (2048, 8192)]
# Phi-3-mini projections: 6 and 16 checkpoint tiles of 512 a row
PHI3_WIDTHS = [(3072, 3072), (8192, 3072), (3072, 8192)]
DECODE_M = 8


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # what this file compiles lands in no cache: the entries could not be
    # read back without a chip
    jax.config.update("jax_enable_compilation_cache", False)
    return SingleDeviceSharding(topo.devices[0])


def _spec(a, sharding):
    return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _prepared(d_out, d_in, n_bits, fmt):
    pk = autotune._synthetic_packed(d_out, d_in, n_bits)
    return backend.prepare(pk, backend="pallas", interpret=False, fmt=fmt)


def _compile_arms(prep, sharding):
    """Compile the fused decode arm and the dequant arm of one weight."""
    p = jax.tree.map(lambda a: _spec(a, sharding), prep)
    x = jax.ShapeDtypeStruct((DECODE_M, prep.pk), jnp.float32,
                             sharding=sharding)
    _compile(lambda x_, p_: backend._fused(
        x_, p_, DECODE_M, prep.block_n, prep.block_k), x, p)
    _compile(lambda p_: backend._dequant_t(
        p_, prep.block_n, prep.block_k), p)


@pytest.mark.parametrize("d_out,d_in", WIDTHS + PHI3_WIDTHS)
def test_icq_v2_kernels_compile_for_v5e(one_chip, d_out, d_in):
    """Fused matmul v2 and dequant v2 at 3 bits, InternLM2 and Phi-3
    widths."""
    _compile_arms(_prepared(d_out, d_in, 3, "v2"), one_chip)


def test_matmul_kernel_lowers_for_tpu(one_chip):
    """The v1 (bitmap plane) fused and dequant kernels compile for v5e."""
    _compile_arms(_prepared(2048, 2048, 4, "v1"), one_chip)


def test_v2_kernels_lower_for_tpu(one_chip):
    """v2 at 2 bits (16 codes per storage word, two bit planes)."""
    _compile_arms(_prepared(2048, 8192, 2, "v2"), one_chip)


def _paged_specs(sharding, *, B, Hkv, G, d, dv, bs, n_pt, d2=0,
                 dtype=jnp.float32):
    nb = B * n_pt + 1
    S = lambda shape, dt=dtype: jax.ShapeDtypeStruct(  # noqa: E731
        shape, dt, sharding=sharding)
    args = [S((B, Hkv, G, d), jnp.float32), S((nb, Hkv, bs, d)),
            S((nb, Hkv, bs, dv)), S((B, n_pt), jnp.int32),
            S((B,), jnp.int32)]
    if d2:
        args += [S((B, Hkv, G, d2), jnp.float32), S((nb, Hkv, bs, d2))]
    return args


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_attention_gqa_compiles_for_v5e(one_chip, dtype):
    """GQA decode over head-major pools: Hkv 8, G 2, hd 128, bs 16."""
    args = _paged_specs(one_chip, B=4, Hkv=8, G=2, d=128, dv=128, bs=16,
                        n_pt=16, dtype=dtype)
    _compile(lambda q, k, v, pg, ln: paged_attention(
        q, k, v, pg, ln, interpret=False), *args)


def test_paged_attention_lowers_for_tpu(one_chip):
    """MLA latent flavor: one latent pool as K and V, rope side channel
    (Hkv 1, G 40, latent 256, rope 32), several pages per grid step."""
    args = _paged_specs(one_chip, B=4, Hkv=1, G=40, d=256, dv=256, bs=16,
                        n_pt=16, d2=32)
    q, c, _, pg, ln, q2, r = args
    _compile(lambda q_, c_, pg_, ln_, q2_, r_: paged_attention(
        q_, c_, c_, pg_, ln_, q2=q2_, k2_pool=r_, pages_per_step=4,
        interpret=False), q, c, pg, ln, q2, r)


def test_prepared_layout_tiles_every_width():
    """Every prepared tensor's blocks are whole (8, 128) tiles (or whole
    dims) at the widths above — the property the compiles rely on."""
    for d_out, d_in in WIDTHS:
        prep = _prepared(d_out, d_in, 3, "v2")
        assert backend.blocks_fit(prep.block_n, prep.block_k, prep.pn,
                                  prep.pk)
        assert prep.block_n % 128 == 0 and prep.block_k % 256 == 0
        assert prep.codes.shape == (3 * prep.pk // 32, prep.pn)
        assert (prep.pn, prep.pk) == (d_out, d_in)     # no padding here
        assert np.all(np.asarray(prep.offs)[-1] > 0)
