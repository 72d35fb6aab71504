"""Pallas paged-attention decode kernel (ISSUE-7) vs the XLA gather arm.

The contract under test: walking the page table *inside* the kernel
(streaming only live blocks through VMEM) computes the same masked
softmax the XLA arm computes over the gathered logical view — to ulp
tolerance at the kernel boundary, and greedy token-identically at the
engine boundary (``ICQ_PAGED_ATTN=pallas|xla``). Fragmented / shuffled
page tables, ragged per-lane lengths, partially-filled tail blocks,
unmapped (-1) tail entries and recycled (kv_len == 0) lanes must all be
invisible to the output, and garbage parked in block 0 (the clamp
target for -1 entries) must never leak into any lane's context.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.kernels.paged_attention import (
    PAGES_PER_STEP_CANDIDATES,
    attn_vmem_bytes,
    fallback_pages_per_step,
    paged_attention,
)

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                                   # pragma: no cover
    HAVE_HYPOTHESIS = False


# ---------------------------------------------------------------------------
# oracle: the XLA arm's math — clamped gather + masked softmax, f64
# ---------------------------------------------------------------------------

def _oracle(q, k_pool, v_pool, pages, kv_len, q2=None, k2_pool=None):
    """f64 plain-softmax attention over the clamped logical gather: the
    same semantics as layers._paged_gather + chunked_attention, computed
    the straightforward way so the kernel's online-softmax reassociation
    is the only difference."""
    B, Hkv, G, d = q.shape
    nb, bs = k_pool.shape[0], k_pool.shape[2]
    T = pages.shape[1] * bs
    pg = np.clip(pages, 0, nb - 1)

    def gather(pool):                   # head-major (nb, Hkv, bs, d) pool
        view = np.swapaxes(pool[pg], 2, 3)       # (B, n_pt, bs, Hkv, d)
        return view.reshape(B, T, Hkv, pool.shape[-1]).astype(np.float64)

    s = np.einsum("bhgd,bthd->bhgt", q.astype(np.float64), gather(k_pool))
    if q2 is not None:
        s += np.einsum("bhgd,bthd->bhgt", q2.astype(np.float64),
                       gather(k2_pool))
    valid = np.arange(T)[None, :] < kv_len[:, None]            # (B, T)
    s = np.where(valid[:, None, None, :], s, -np.inf)
    m = np.where(kv_len[:, None, None] > 0, s.max(-1), 0.0)[..., None]
    p = np.where(valid[:, None, None, :], np.exp(s - m), 0.0)
    l = p.sum(-1, keepdims=True)
    ctx = np.einsum("bhgt,bthd->bhgd", p, gather(v_pool))
    return (ctx / np.maximum(l, 1e-30)).astype(np.float32)


def _case(rng, B, Hkv, G, d, dv, bs, n_pt, nb, kv_len, *, d2=0,
          avoid_block0=False):
    """Random operands with a shuffled, fragmented page table: lanes
    interleave through a block permutation, unmapped tail entries are
    -1, and ``avoid_block0`` keeps every live page >= 1 so block 0 can
    be scrambled as the clamp-garbage probe."""
    q = rng.standard_normal((B, Hkv, G, d)).astype(np.float32)
    k_pool = rng.standard_normal((nb, Hkv, bs, d)).astype(np.float32)
    v_pool = rng.standard_normal((nb, Hkv, bs, dv)).astype(np.float32)
    q2 = k2_pool = None
    if d2:
        q2 = rng.standard_normal((B, Hkv, G, d2)).astype(np.float32)
        k2_pool = rng.standard_normal((nb, Hkv, bs, d2)).astype(np.float32)
    kv_len = np.asarray(kv_len, np.int32)
    blocks = np.arange(1, nb) if avoid_block0 else np.arange(nb)
    perm = rng.permutation(blocks)
    pages = np.full((B, n_pt), -1, np.int32)
    take = 0
    for i in range(B):
        need = -(-int(kv_len[i]) // bs)
        pages[i, :need] = perm[take: take + need]
        take += need
    assert take <= len(perm), "test case maps more blocks than the pool"
    return q, k_pool, v_pool, pages, kv_len, q2, k2_pool


def _kernel_out(q, k_pool, v_pool, pages, kv_len, q2, k2_pool, pps):
    return np.asarray(paged_attention(
        jnp.asarray(q), jnp.asarray(k_pool), jnp.asarray(v_pool),
        jnp.asarray(pages), jnp.asarray(kv_len),
        q2=None if q2 is None else jnp.asarray(q2),
        k2_pool=None if k2_pool is None else jnp.asarray(k2_pool),
        pages_per_step=pps))


# ---------------------------------------------------------------------------
# deterministic parity sweeps (interpret mode — run everywhere)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("pps", [1, 2, 8])
def test_gqa_parity_ragged_lanes(pps):
    """GQA flavor: ragged kv_len (full blocks, partial tail, single row,
    recycled kv_len=0 lane) x every pages-per-step shape, vs the f64
    oracle to f32-ulp-scale tolerance."""
    rng = np.random.default_rng(pps)
    case = _case(rng, B=4, Hkv=2, G=2, d=8, dv=8, bs=4, n_pt=4, nb=20,
                 kv_len=[16, 7, 1, 0])
    out = _kernel_out(*case, pps)
    ref = _oracle(*case[:5], q2=case[5], k2_pool=case[6])
    np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)
    assert np.all(out[3] == 0.0)            # recycled lane -> exact zeros


@pytest.mark.parametrize("pps", [1, 2])
def test_mla_rope_sidechannel_parity(pps):
    """MLA flavor: Hkv=1, the latent pool doubles as K and V, rope
    halves ride the q2/k2 score pair."""
    rng = np.random.default_rng(10 + pps)
    q, c_pool, _, pages, kv_len, q2, r_pool = _case(
        rng, B=3, Hkv=1, G=4, d=8, dv=8, bs=4, n_pt=3, nb=12,
        kv_len=[10, 4, 3], d2=4)
    out = _kernel_out(q, c_pool, c_pool, pages, kv_len, q2, r_pool, pps)
    ref = _oracle(q, c_pool, c_pool, pages, kv_len, q2=q2, k2_pool=r_pool)
    np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)


def test_block0_garbage_is_invisible():
    """-1 page entries clamp to block 0, so block 0 is the one block a
    fragmented pool can hand any lane uninvited: scrambling it (huge
    finite values) must leave every output bitwise unchanged when no
    live page maps it."""
    rng = np.random.default_rng(3)
    q, k_pool, v_pool, pages, kv_len, _, _ = _case(
        rng, B=3, Hkv=2, G=2, d=8, dv=8, bs=4, n_pt=3, nb=12,
        kv_len=[9, 4, 0], avoid_block0=True)
    base = _kernel_out(q, k_pool, v_pool, pages, kv_len, None, None, 2)
    k_pool[0] = 1e9
    v_pool[0] = -1e9
    poisoned = _kernel_out(q, k_pool, v_pool, pages, kv_len, None, None, 2)
    assert np.array_equal(base.view(np.uint8), poisoned.view(np.uint8))


def test_rejects_lone_rope_operand():
    rng = np.random.default_rng(0)
    q, k_pool, v_pool, pages, kv_len, q2, _ = _case(
        rng, B=1, Hkv=1, G=1, d=4, dv=4, bs=2, n_pt=2, nb=4,
        kv_len=[3], d2=4)
    with pytest.raises(ValueError):
        paged_attention(jnp.asarray(q), jnp.asarray(k_pool),
                        jnp.asarray(v_pool), jnp.asarray(pages),
                        jnp.asarray(kv_len), q2=jnp.asarray(q2))


# ---------------------------------------------------------------------------
# property test: fragmented tables / ragged lengths / recycled lanes
# ---------------------------------------------------------------------------

if HAVE_HYPOTHESIS:
    @settings(max_examples=20, deadline=None)
    @given(seed=st.integers(0, 2**31 - 1),
           B=st.integers(1, 3),
           hkv_g=st.sampled_from([(1, 4), (2, 2), (2, 1)]),
           bs=st.sampled_from([2, 4]),
           n_pt=st.integers(1, 4),
           pps=st.sampled_from(PAGES_PER_STEP_CANDIDATES),
           mla=st.booleans())
    def test_property_kernel_matches_oracle(seed, B, hkv_g, bs, n_pt, pps,
                                            mla):
        """Any shuffled/fragmented table, any ragged kv_len mix (partial
        tails, unmapped -1 tails, recycled lanes), any pages-per-step:
        kernel == oracle to f32-ulp-scale tolerance."""
        Hkv, G = (1, 4) if mla else hkv_g
        rng = np.random.default_rng(seed)
        kv_len = rng.integers(0, n_pt * bs + 1, B)
        nb = int(sum(-(-int(n) // bs) for n in kv_len)) + 2
        case = _case(rng, B=B, Hkv=Hkv, G=G, d=8, dv=8, bs=bs, n_pt=n_pt,
                     nb=nb, kv_len=kv_len, d2=4 if mla else 0)
        if mla:
            q, c_pool, _, pages, kv_len, q2, r_pool = case
            out = _kernel_out(q, c_pool, c_pool, pages, kv_len, q2,
                              r_pool, pps)
            ref = _oracle(q, c_pool, c_pool, pages, kv_len, q2=q2,
                          k2_pool=r_pool)
        else:
            out = _kernel_out(*case, pps)
            ref = _oracle(*case[:5])
        np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)
        assert np.all(out[np.asarray(kv_len) == 0] == 0.0)


# ---------------------------------------------------------------------------
# VMEM accounting (the TPU compile of this kernel is in test_tpu_compile.py)
# ---------------------------------------------------------------------------

def test_vmem_fallback_respects_budget():
    """fallback_pages_per_step picks the largest sweep candidate that
    fits, never exceeds n_pt, and floors at 1 under absurd budgets."""
    kw = dict(G=4, d=64, dv=64, bs=16, d2=0, itemsize=4)
    per_page = 2 * kw["bs"] * (kw["d"] + kw["dv"]) * 4   # double-buffered
    assert (attn_vmem_bytes(2, **{k: v for k, v in kw.items()
                                  if k != "itemsize"})
            - attn_vmem_bytes(1, **{k: v for k, v in kw.items()
                                    if k != "itemsize"})) == per_page
    roomy = attn_vmem_bytes(8, **{k: v for k, v in kw.items()
                                  if k != "itemsize"})
    assert fallback_pages_per_step(n_pt=32, budget=roomy, **kw) == 8
    assert fallback_pages_per_step(n_pt=3, budget=roomy, **kw) == 2
    assert fallback_pages_per_step(n_pt=32, budget=1, **kw) == 1


def test_autotune_key_and_cache_roundtrip(tmp_path, monkeypatch):
    """The pages-per-step pick flows through the same JSON autotune cache
    as the matmul blocks: a pinned entry wins over the VMEM fallback."""
    from repro.kernels import autotune

    from repro.kernels.platform import default_interpret

    monkeypatch.setenv("ICQ_AUTOTUNE_CACHE", str(tmp_path / "tune.json"))
    autotune.reset()
    kw = dict(G=4, d=8, dv=8, bs=4, n_pt=4, d2=0, itemsize=4)
    key = autotune.paged_attn_key(4, 8, 8, 4, 4, d2=0,
                                  interpret=default_interpret())
    assert key.startswith("paged_attn/")
    assert autotune.paged_attn_pages_per_step(**kw) == \
        fallback_pages_per_step(**kw)
    autotune.record(key, [1])
    assert autotune.paged_attn_pages_per_step(**kw) == 1
    autotune.reset()


def test_autotune_sweep_records_winner(tmp_path, monkeypatch):
    from repro.kernels import autotune

    monkeypatch.setenv("ICQ_AUTOTUNE_CACHE", str(tmp_path / "tune.json"))
    autotune.reset()
    got = autotune.autotune_paged_attn(2, 1, 4, 8, 8, 4, 2,
                                       interpret=True,
                                       candidates=[2, 1], iters=1)
    assert not got["cached"] and got["pages_per_step"] in (1, 2)
    again = autotune.autotune_paged_attn(2, 1, 4, 8, 8, 4, 2,
                                         interpret=True)
    assert again["cached"] and again["pages_per_step"] == \
        got["pages_per_step"]
    autotune.reset()


# ---------------------------------------------------------------------------
# dispatch + engine-level token identity across arms
# ---------------------------------------------------------------------------

def test_arm_dispatch(monkeypatch):
    from repro.kernels import backend
    from repro.models.layers import _paged_attn_arm

    monkeypatch.setenv("ICQ_PAGED_ATTN", "pallas")
    assert _paged_attn_arm(1, 0, 16) == "pallas"
    assert _paged_attn_arm(1, 32, 16) == "pallas"   # window >= T: inactive
    assert _paged_attn_arm(4, 0, 16) == "xla"       # chunk steps: gather arm
    assert _paged_attn_arm(1, 8, 16) == "xla"       # active sliding window
    with backend.forced_backend("xla"):             # fault degrade pin
        assert _paged_attn_arm(1, 0, 16) == "xla"
    monkeypatch.setenv("ICQ_PAGED_ATTN", "xla")
    assert _paged_attn_arm(1, 0, 16) == "xla"
    monkeypatch.setenv("ICQ_PAGED_ATTN", "mosaic")
    with pytest.raises(ValueError):
        _paged_attn_arm(1, 0, 16)


@pytest.mark.parametrize("arch", ["llama3.2-1b", "minicpm3-4b"])
def test_engine_greedy_token_identical_across_arms(arch, monkeypatch):
    """Greedy paged serving must emit identical token streams whichever
    arm computes decode attention (pallas in interpret mode here), under
    both the fused one-launch structure and the split chunk+decode
    structure."""
    from repro.configs import get_config, smoke_variant
    from repro.models import init_model
    from repro.serving import GenerationEngine, Request

    cfg = smoke_variant(get_config(arch))
    params = init_model(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(5)
    specs = [dict(rid=rid,
                  prompt=rng.integers(0, cfg.vocab_size,
                                      int(rng.integers(2, 9))
                                      ).astype(np.int32),
                  max_new_tokens=int(rng.integers(2, 6)))
             for rid in range(3)]
    out = {}
    for arm in ("xla", "pallas"):
        monkeypatch.setenv("ICQ_PAGED_ATTN", arm)
        for label, kw in ((arm, {}), (f"{arm}_split",
                                      dict(fused_step=False))):
            eng = GenerationEngine(params, cfg, batch_size=2, max_len=24,
                                   mode="continuous", kv_layout="paged",
                                   kv_block_size=4, prefill_chunk=4, **kw)
            for s in specs:
                eng.submit(Request(**s))
            out[label] = {rid: r.generated
                          for rid, r in eng.run().items()}
    assert (out["pallas"] == out["pallas_split"] == out["xla"]
            == out["xla_split"])
    assert all(len(v) > 0 for v in out["pallas"].values())
