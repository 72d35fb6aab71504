"""Paged KV-cache subsystem (ISSUE-5): block-pool allocator invariants,
paged-vs-contiguous bitwise cache/logits parity for gqa + mla, engine
token identity (including chunked prefill, recycled slots and
preempt-and-requeue), and the layout/env knobs.

The contract under test: paging changes *where* cache rows live (and how
much HBM they charge), never what any sampled token sees — greedy paged
output must be token-identical to the contiguous per-lane cache, even
when the pool is small enough that lanes get preempted and recomputed.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config, smoke_variant
from repro.launch.steps import (
    make_cache,
    sync_cache_pages,
    sync_cache_positions,
)
from repro.models import init_model
from repro.models.model import lm_apply
from repro.serving import GenerationEngine, KVBlockPool, Request


def _setup(arch):
    cfg = smoke_variant(get_config(arch))
    params = init_model(jax.random.PRNGKey(0), cfg)
    return cfg, params


# ---------------------------------------------------------------------------
# allocator: property-style invariants (no model)
# ---------------------------------------------------------------------------

def test_pool_alloc_free_reclaim_invariants_random_schedule():
    """Random ensure/grow/release schedule: no double-assignment, free-list
    conservation, full reclaim — checked after every operation."""
    rng = np.random.default_rng(0)
    pool = KVBlockPool(num_blocks=13, block_size=4, n_lanes=3,
                       max_blocks_per_lane=5)
    tokens = [0, 0, 0]
    for _ in range(300):
        lane = int(rng.integers(3))
        if rng.random() < 0.3:
            owned = pool.lane_blocks(lane)
            assert pool.release(lane) == owned   # full reclaim, same call
            tokens[lane] = 0
        else:
            want = tokens[lane] + int(rng.integers(1, 6))
            backed = pool.grow(lane, want)
            assert backed == min(pool.lane_blocks(lane) * 4, 20)
            assert backed <= 20                       # page-table cap
            tokens[lane] = min(want, backed)
        pool.check_invariants()
        assert pool.free_blocks + pool.used_blocks == 13
    for lane in range(3):
        pool.release(lane)
    pool.check_invariants()
    assert pool.free_blocks == 13


def test_pool_release_returns_all_blocks_same_call():
    pool = KVBlockPool(num_blocks=8, block_size=2, n_lanes=2,
                       max_blocks_per_lane=4)
    assert pool.ensure(0, 7)          # 4 blocks
    assert pool.ensure(1, 3)          # 2 blocks
    assert pool.used_blocks == 6
    assert pool.release(0) == 4       # every block back, immediately
    assert pool.free_blocks == 6
    assert (pool.table[0] == -1).all()
    pool.check_invariants()


def test_pool_exhaustion_reports_shortfall_without_corruption():
    pool = KVBlockPool(num_blocks=3, block_size=4, n_lanes=2,
                       max_blocks_per_lane=4)
    assert pool.grow(0, 12) == 12     # 3 blocks: pool drained
    assert not pool.ensure(1, 4)      # nothing left for lane 1
    assert pool.grow(1, 4) == 0
    pool.check_invariants()
    pool.release(0)
    assert pool.ensure(1, 4)          # freed blocks immediately reusable
    pool.check_invariants()


def test_pool_page_table_is_logical_order_and_versioned():
    pool = KVBlockPool(num_blocks=6, block_size=2, n_lanes=2,
                       max_blocks_per_lane=3)
    v0 = pool.version
    pool.ensure(0, 5)                 # 3 blocks
    assert pool.version > v0
    row = pool.table[0]
    assert (row[:3] >= 0).all()
    assert len(set(row[:3].tolist())) == 3
    v1 = pool.version
    pool.ensure(0, 5)                 # no growth needed -> no version bump
    assert pool.version == v1


def test_pool_rejects_bad_shapes():
    for bad in (dict(num_blocks=0), dict(block_size=0), dict(n_lanes=0),
                dict(max_blocks_per_lane=0)):
        kw = dict(num_blocks=4, block_size=4, n_lanes=2,
                  max_blocks_per_lane=2)
        kw.update(bad)
        with pytest.raises(ValueError):
            KVBlockPool(**kw)


# ---------------------------------------------------------------------------
# allocator: refcounted sharing (prefix cache / sessions, ISSUE-8)
# ---------------------------------------------------------------------------

def _pin_and_release(pool, lane):
    """The engine's retain-at-finish ritual: pin the lane's chain with
    one external reference each, *then* release the lane — so the blocks
    stay live through the hand-off (never transiting refcount 0)."""
    chain = pool.lane_chain(lane)
    for b in chain:
        pool.incref(b)
    pool.release(lane)
    return chain


def test_pool_external_pin_survives_release():
    pool = KVBlockPool(num_blocks=8, block_size=4, n_lanes=2,
                       max_blocks_per_lane=4)
    pool.grow(0, 8)                       # 2 blocks
    chain = _pin_and_release(pool, 0)
    ext = {b: 1 for b in chain}
    pool.check_invariants(external=ext)
    assert pool.used_blocks == len(chain)  # pins alone keep them live
    # a later lane maps the pinned chain without copying: ref -> 2
    pool.share(1, chain)
    assert pool.shared_blocks() == len(chain)
    pool.check_invariants(external=ext)
    pool.release(1)
    pool.check_invariants(external=ext)
    for b in chain:                       # cache eviction analog
        pool.decref(b)
    pool.check_invariants()
    assert pool.free_blocks == 8


def test_pool_cow_fork_remaps_and_preserves_source():
    pool = KVBlockPool(num_blocks=6, block_size=4, n_lanes=2,
                       max_blocks_per_lane=4)
    pool.grow(0, 8)
    chain = _pin_and_release(pool, 0)
    pool.share(1, chain)
    v = pool.version
    dst = pool.fork(1, 1)
    assert dst is not None and dst not in chain
    assert pool.version > v
    assert pool.lane_chain(1) == [chain[0], dst]
    assert pool.table[1, 1] == dst
    assert pool.refcount(chain[1]) == 1   # only the external pin remains
    assert pool.refcount(dst) == 1        # lane-private, writable
    pool.check_invariants(external={b: 1 for b in chain})


def test_pool_fork_dry_pool_degrades_via_pop_last():
    pool = KVBlockPool(num_blocks=2, block_size=4, n_lanes=2,
                       max_blocks_per_lane=2)
    pool.grow(0, 8)
    chain = _pin_and_release(pool, 0)
    pool.share(1, chain)
    assert pool.fork(1, 1) is None        # nothing left to fork into
    assert pool.pop_last(1) == chain[1]   # degrade: drop the tail mapping
    assert pool.lane_chain(1) == [chain[0]]
    pool.check_invariants(external={b: 1 for b in chain})


def test_pool_refcount_guards():
    pool = KVBlockPool(num_blocks=4, block_size=2, n_lanes=2,
                       max_blocks_per_lane=2)
    with pytest.raises(ValueError):
        pool.incref(0)                    # pinning a free block = garbage
    with pytest.raises(ValueError):
        pool.decref(0)
    with pytest.raises(ValueError):
        pool.incref(99)
    pool.grow(0, 2)
    b = pool.lane_chain(0)[0]
    pool.incref(b)
    assert not pool.decref(b)             # still lane-mapped: not freed
    assert pool.release(0) == 1
    assert pool.free_blocks == 4
    with pytest.raises(ValueError):
        pool.share(1, [b])                # sharing a freed block
    pool.grow(0, 2)
    with pytest.raises(ValueError):
        pool.share(0, pool.lane_chain(0))  # share into a non-empty lane


def _random_share_schedule(pool, rng, steps):
    """Random grow/release/retain/share/fork/evict schedule mirroring the
    engine's prefix-cache lifecycle; invariants checked every step."""
    bs = pool.block_size
    tokens = [0] * pool.n_lanes
    external = {}
    retained = []

    def unpin(chain):
        for b in reversed(chain):
            external[b] -= 1
            if external[b] == 0:
                del external[b]
            pool.decref(b)

    for _ in range(steps):
        op = rng.random()
        lane = int(rng.integers(pool.n_lanes))
        if op < 0.25:                              # finish: maybe retain
            chain = pool.lane_chain(lane)
            if chain and rng.random() < 0.5:
                for b in chain:
                    pool.incref(b)
                    external[b] = external.get(b, 0) + 1
                retained.append(chain)
            pool.release(lane)
            tokens[lane] = 0
        elif op < 0.5 and retained and pool.lane_blocks(lane) == 0:
            chain = retained[int(rng.integers(len(retained)))]
            k = int(rng.integers(1, len(chain) + 1))
            k = min(k, pool.max_blocks_per_lane)
            pool.share(lane, chain[:k])            # warm start
            tokens[lane] = k * bs
            if rng.random() < 0.5:                 # mid-block divergence
                pool.fork(lane, k - 1)
        elif op < 0.65 and retained:               # eviction analog
            unpin(retained.pop(int(rng.integers(len(retained)))))
        else:
            want = tokens[lane] + int(rng.integers(1, 2 * bs + 1))
            tokens[lane] = min(want, pool.grow(lane, want))
        pool.check_invariants(external=external)
        assert pool.free_blocks + pool.used_blocks == pool.num_blocks
    for lane in range(pool.n_lanes):
        pool.release(lane)
    while retained:
        unpin(retained.pop())
    pool.check_invariants()
    assert pool.free_blocks == pool.num_blocks


def test_pool_refcount_invariants_random_share_schedule():
    rng = np.random.default_rng(3)
    pool = KVBlockPool(num_blocks=16, block_size=4, n_lanes=4,
                       max_blocks_per_lane=4)
    _random_share_schedule(pool, rng, 400)


# ---------------------------------------------------------------------------
# allocator: trim — the speculative-decoding rollback primitive (ISSUE-10)
# ---------------------------------------------------------------------------

def test_pool_trim_across_block_boundaries():
    """Trim pops exactly the tail blocks past blocks_for(new_len):
    block-aligned and mid-block targets, idempotence, trim-to-zero."""
    pool = KVBlockPool(num_blocks=8, block_size=4, n_lanes=2,
                       max_blocks_per_lane=4)
    assert pool.grow(0, 14) == 16            # 4 blocks mapped
    chain = pool.lane_chain(0)
    with pytest.raises(ValueError):
        pool.trim(0, -1)
    v = pool.version
    assert pool.trim(0, 9) == 1              # mid-block: keep 3 blocks
    assert pool.version > v
    assert pool.lane_chain(0) == chain[:3]
    assert (pool.table[0, 3:] == -1).all()
    pool.check_invariants()
    v = pool.version
    assert pool.trim(0, 9) == 0              # idempotent, no version bump
    assert pool.trim(0, 12) == 0             # growing target is a no-op
    assert pool.version == v
    assert pool.trim(0, 8) == 1              # block-aligned: keep 2
    assert pool.trim(0, 1) == 1              # keep the partial head block
    assert pool.lane_chain(0) == chain[:1]
    assert pool.trim(0, 0) == 1              # full rewind
    assert pool.lane_blocks(0) == 0
    pool.check_invariants()
    assert pool.free_blocks == 8             # every popped block recycled
    assert pool.ensure(1, 16)                # ... and immediately reusable


def test_pool_trim_shared_tail_drops_mapping_never_contents():
    """Trim over a shared (prefix-cache pinned) chain: the lane's mapping
    goes, the blocks stay live under their pins — never recycled, so the
    chain another lane attends through is structurally untouchable."""
    pool = KVBlockPool(num_blocks=8, block_size=4, n_lanes=2,
                       max_blocks_per_lane=4)
    pool.grow(0, 12)                         # 3 blocks
    chain = _pin_and_release(pool, 0)
    ext = {b: 1 for b in chain}
    pool.share(1, chain)
    assert pool.trim(1, 4) == 2              # drop two shared tail mappings
    assert pool.lane_chain(1) == chain[:1]
    for b in chain:                          # all three survive their pins
        assert pool.refcount(b) >= 1
    assert pool.used_blocks == 3
    pool.check_invariants(external=ext)
    pool.release(1)
    for b in chain:
        pool.decref(b)
    pool.check_invariants()
    assert pool.free_blocks == 8


def test_pool_trim_after_cow_fork_frees_private_block_only():
    """COW fork then trim — the speculative divergence-inside-a-shared-
    block shape: the lane's private forked block is recycled by trim,
    the pinned original it replaced is not."""
    pool = KVBlockPool(num_blocks=8, block_size=4, n_lanes=2,
                       max_blocks_per_lane=4)
    pool.grow(0, 8)                          # 2 blocks
    chain = _pin_and_release(pool, 0)
    ext = {b: 1 for b in chain}
    pool.share(1, chain)
    dst = pool.fork(1, 1)                    # diverge in the tail block
    assert dst is not None and pool.refcount(dst) == 1
    free_before = pool.free_blocks
    assert pool.trim(1, 4) == 1              # rejection rewinds the fork
    assert pool.free_blocks == free_before + 1   # dst recycled...
    assert pool.refcount(chain[1]) == 1          # ...the original only pinned
    assert pool.lane_chain(1) == [chain[0]]
    pool.check_invariants(external=ext)


def _random_spec_schedule(pool, rng, steps, spec_k=4):
    """Random draft/accept/reject schedule mirroring the speculative
    engine's per-iteration KV lifecycle: grow to back pos + k + 1 before
    the verify launch, trim back to pos + emitted + 1 afterwards —
    interleaved with finishes, warm-start shares and COW forks.
    Invariants checked after every operation."""
    bs = pool.block_size
    cap = pool.max_blocks_per_lane * bs
    pos = [0] * pool.n_lanes
    external = {}
    retained = []

    def unpin(chain):
        for b in reversed(chain):
            external[b] -= 1
            if external[b] == 0:
                del external[b]
            pool.decref(b)

    for _ in range(steps):
        op = rng.random()
        lane = int(rng.integers(pool.n_lanes))
        if op < 0.15:                              # finish: maybe retain
            chain = pool.lane_chain(lane)
            if chain and rng.random() < 0.5:
                for b in chain:
                    pool.incref(b)
                    external[b] = external.get(b, 0) + 1
                retained.append(chain)
            pool.release(lane)
            pos[lane] = 0
        elif op < 0.3 and retained and pool.lane_blocks(lane) == 0:
            chain = retained[int(rng.integers(len(retained)))]
            k = int(rng.integers(
                1, min(len(chain), pool.max_blocks_per_lane) + 1))
            pool.share(lane, chain[:k])            # warm start on a prefix
            pos[lane] = k * bs
            if rng.random() < 0.5:                 # mid-block divergence
                if pool.fork(lane, k - 1) is None:
                    pool.pop_last(lane)            # dry-pool degrade
                    pos[lane] = (k - 1) * bs
        elif op < 0.4 and retained:                # cache eviction analog
            unpin(retained.pop(int(rng.integers(len(retained)))))
        else:                                      # draft -> verify -> accept
            k = int(rng.integers(1, spec_k + 1))
            want = min(pos[lane] + k + 1, cap)
            backed = pool.grow(lane, want)
            if backed <= pos[lane]:                # pool dry: preempt
                pool.release(lane)
                pos[lane] = 0
            else:
                k = min(k, backed - pos[lane] - 1)  # clip, never preempt
                emitted = int(rng.integers(1, k + 2))   # accept a in [0, k]
                new_pos = min(pos[lane] + emitted, backed)
                pool.trim(lane, new_pos + 1)       # keep the next-write row
                pos[lane] = min(new_pos, cap - 1)
        pool.check_invariants(external=external)
        assert pool.free_blocks + pool.used_blocks == pool.num_blocks
        for ln in range(pool.n_lanes):             # every pos stays backed
            assert pool.lane_blocks(ln) * bs >= pos[ln]
    for lane in range(pool.n_lanes):
        pool.release(lane)
    while retained:
        unpin(retained.pop())
    pool.check_invariants()
    assert pool.free_blocks == pool.num_blocks


def test_pool_trim_invariants_random_spec_schedule():
    rng = np.random.default_rng(5)
    pool = KVBlockPool(num_blocks=16, block_size=4, n_lanes=4,
                       max_blocks_per_lane=4)
    _random_spec_schedule(pool, rng, 400)


# ---------------------------------------------------------------------------
# layer-level: paged cache == contiguous cache, bitwise (gqa + mla)
# ---------------------------------------------------------------------------

def _attn_leaves(cache):
    return cache["stack"]["attn"]


def _logical_view(leaf, pages, bs):
    """Head-major (num_blocks, H, bs, d) pool + (B, n_pt) table ->
    (B, n_pt*bs, H, d) logical rows."""
    a = np.asarray(leaf)
    pg = np.clip(np.asarray(pages), 0, a.shape[0] - 1)
    v = np.swapaxes(a[pg], 2, 3)                  # (B, n_pt, bs, H, d)
    return v.reshape((pg.shape[0], pg.shape[1] * bs) + v.shape[3:])


@pytest.mark.parametrize("arch", ["llama3.2-1b", "minicpm3-4b"])
def test_paged_walk_bitwise_cache_and_logits(arch):
    """Walk identical chunked prompts through a contiguous per-lane cache
    and a paged cache with a *shuffled* physical block assignment: every
    valid logical cache row and the next-token logits must match the
    contiguous cache bitwise (gqa K/V pool and mla latent pool)."""
    cfg, params = _setup(arch)
    B, L, S, bs = 3, 16, 4, 4
    n_pt = L // bs
    rng = np.random.default_rng(0)
    plens = [8, 5, 6]
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in plens]
    # non-identity mapping: lanes interleave through a 14-block pool
    nb = 14
    perm = rng.permutation(nb)
    pages = np.full((B, n_pt), -1, np.int32)
    for i in range(B):
        need = -(-plens[i] // bs) + 1     # one spare block mapped
        pages[i, :need] = perm[i::B][:need]
    d_pages = jnp.asarray(pages)

    def walk(cache, paged):
        consumed = np.zeros(B, np.int32)
        for _ in range(2):
            lens = np.zeros(B, np.int32)
            toks = np.zeros((B, S), np.int32)
            for i in range(B):
                n = min(S, plens[i] - consumed[i])
                if n > 0:
                    toks[i, :n] = prompts[i][consumed[i]: consumed[i] + n]
                    lens[i] = n
            c = sync_cache_positions(cache, jnp.asarray(consumed.copy()))
            if paged:
                c = sync_cache_pages(c, d_pages)
            _, cache, _ = lm_apply(params, cfg, jnp.asarray(toks), cache=c,
                                   start_pos=jnp.asarray(consumed.copy()),
                                   seq_lens=jnp.asarray(lens))
            consumed += lens
        assert list(consumed) == plens
        return cache

    cache_c = walk(make_cache(params, cfg, B, L, per_lane=True), False)
    cache_p = walk(make_cache(params, cfg, B, L, per_lane=True,
                              paged=(nb, bs)), True)

    for name, leaf in _attn_leaves(cache_c).items():
        if name == "index":
            continue
        a = np.asarray(leaf)                          # (Lyr, B, L, ...)
        pleaf = _attn_leaves(cache_p)[name]
        for lyr in range(a.shape[0]):
            b = _logical_view(pleaf[lyr], pages, bs)
            for i in range(B):
                va, vb = a[lyr, i, : plens[i]], b[i, : plens[i]]
                vb = vb.reshape(va.shape)     # mla: drop the 1-head axis
                assert np.array_equal(va.view(np.uint8),
                                      vb.view(np.uint8)), (
                    f"{name}: paged lane {i} cache rows diverge bitwise")

    # next-token logits: what the first generated token would see
    nxt = rng.integers(0, cfg.vocab_size, (B, 1)).astype(np.int32)
    pos = np.asarray(plens, np.int32)

    def logits(cache, paged):
        c = sync_cache_positions(cache, jnp.asarray(pos))
        if paged:
            c = sync_cache_pages(c, d_pages)
        return np.asarray(lm_apply(params, cfg, jnp.asarray(nxt), cache=c,
                                   start_pos=jnp.asarray(pos))[0])

    l_c, l_p = logits(cache_c, False), logits(cache_p, True)
    assert np.array_equal(l_c.view(np.uint8), l_p.view(np.uint8))


def test_paged_cache_requires_per_lane():
    cfg, params = _setup("llama3.2-1b")
    with pytest.raises(NotImplementedError):
        make_cache(params, cfg, 2, 16, per_lane=False, paged=(8, 4))


# ---------------------------------------------------------------------------
# engine-level: token identity + reclaim + preemption
# ---------------------------------------------------------------------------

def _mixed_specs(cfg, n, seed=0, prompt_hi=9, new_hi=8):
    rng = np.random.default_rng(seed)
    return [dict(rid=rid,
                 prompt=rng.integers(0, cfg.vocab_size,
                                     int(rng.integers(2, prompt_hi))
                                     ).astype(np.int32),
                 max_new_tokens=int(rng.integers(2, new_hi)))
            for rid in range(n)]


def _run(params, cfg, specs, **kw):
    eng = GenerationEngine(params, cfg, batch_size=2, max_len=32,
                           mode="continuous", **kw)
    for s in specs:
        eng.submit(Request(**s))
    out = {rid: r.generated for rid, r in eng.run().items()}
    return out, eng


@pytest.mark.parametrize("arch", ["llama3.2-1b", "minicpm3-4b"])
def test_engine_paged_greedy_token_identical(arch):
    """contiguous == paged == paged+chunked-prefill, per request, with
    more requests than slots so recycled slots re-map fresh blocks."""
    cfg, params = _setup(arch)
    specs = _mixed_specs(cfg, 5)
    out = {}
    runs = (
        ("contig", dict(kv_layout="contiguous")),
        ("paged", dict(kv_layout="paged", kv_block_size=4)),
        ("paged_chunk", dict(kv_layout="paged", kv_block_size=4,
                             prefill_chunk=4)),
        ("paged_offcap", dict(kv_layout="paged", kv_block_size=5)),
    )
    for label, kw in runs:
        out[label], eng = _run(params, cfg, specs, **kw)
        if eng._pool is not None:
            eng._pool.check_invariants()
            # every lane finished -> every block reclaimed
            assert eng._pool.free_blocks == eng._pool.num_blocks
    assert (out["paged"] == out["paged_chunk"] == out["paged_offcap"]
            == out["contig"])


@pytest.mark.parametrize("arch", ["llama3.2-1b", "minicpm3-4b"])
def test_engine_preemption_recomputes_identical_streams(arch):
    """Pool sized so two long-running lanes cannot both finish: the
    youngest lane is preempted, requeued at the queue head, and its
    greedy stream must still match the contiguous run token-for-token."""
    cfg, params = _setup(arch)
    rng = np.random.default_rng(1)
    specs = [dict(rid=r,
                  prompt=rng.integers(0, cfg.vocab_size, 4).astype(np.int32),
                  max_new_tokens=16) for r in range(2)]
    out_c, _ = _run(params, cfg, specs, kv_layout="contiguous")
    out_p, eng = _run(params, cfg, specs, kv_layout="paged",
                      kv_block_size=4, kv_blocks=6)
    assert eng.metrics.preemptions >= 1, \
        "pool was large enough that nothing was preempted — bad fixture"
    assert out_p == out_c
    assert eng.metrics.requests[1].n_preempted >= 1
    assert eng._pool.free_blocks == eng._pool.num_blocks


def test_engine_double_preemption_folds_only_fresh_tokens():
    """A request preempted TWICE must fold only the not-yet-folded
    generated suffix into its prompt each time — double-folding would
    duplicate tokens in the replay and diverge from contiguous. Three
    lanes: the short lane finishes and frees blocks, the youngest long
    lane is readmitted mid-run and preempted a second time."""
    cfg, params = _setup("llama3.2-1b")
    rng = np.random.default_rng(1)
    specs = [dict(rid=0, prompt=rng.integers(0, cfg.vocab_size, 4)
                  .astype(np.int32), max_new_tokens=8),
             dict(rid=1, prompt=rng.integers(0, cfg.vocab_size, 4)
                  .astype(np.int32), max_new_tokens=24),
             dict(rid=2, prompt=rng.integers(0, cfg.vocab_size, 4)
                  .astype(np.int32), max_new_tokens=24)]
    eng_c = GenerationEngine(params, cfg, batch_size=3, max_len=32,
                             mode="continuous", kv_layout="contiguous")
    for s in specs:
        eng_c.submit(Request(**s))
    out_c = {rid: r.generated for rid, r in eng_c.run().items()}
    eng_p = GenerationEngine(params, cfg, batch_size=3, max_len=32,
                             mode="continuous", kv_layout="paged",
                             kv_block_size=4, kv_blocks=8)
    for s in specs:
        eng_p.submit(Request(**s))
    out_p = {rid: r.generated for rid, r in eng_p.run().items()}
    assert eng_p.metrics.requests[2].n_preempted >= 2, \
        "fixture no longer produces a double preemption"
    assert out_p == out_c
    eng_p._pool.check_invariants()
    assert eng_p._pool.free_blocks == eng_p._pool.num_blocks


def test_engine_paged_uses_less_cache_hbm():
    cfg, params = _setup("llama3.2-1b")
    specs = _mixed_specs(cfg, 3)
    _, eng_c = _run(params, cfg, specs, kv_layout="contiguous")
    _, eng_p = _run(params, cfg, specs, kv_layout="paged",
                    kv_block_size=4, kv_blocks=8)
    assert eng_p.metrics.cache_bytes < eng_c.metrics.cache_bytes
    s = eng_p.metrics.summary()
    assert s["kv_blocks"] == 8 and s["kv_block_size"] == 4
    assert 0 < s["mean_block_utilization"] <= 1
    assert s["peak_blocks_in_use"] <= 8


def test_engine_rejects_unservable_paged_request():
    """A request whose prompt + budget can never fit the pool alone must
    be rejected at submit (otherwise preemption could livelock)."""
    cfg, params = _setup("llama3.2-1b")
    eng = GenerationEngine(params, cfg, batch_size=2, max_len=32,
                           mode="continuous", kv_layout="paged",
                           kv_block_size=4, kv_blocks=3)
    with pytest.raises(ValueError, match="KV blocks"):
        eng.submit(Request(0, np.zeros(10, np.int32), max_new_tokens=8))
    # the same request fits a bigger pool
    eng2 = GenerationEngine(params, cfg, batch_size=2, max_len=32,
                            mode="continuous", kv_layout="paged",
                            kv_block_size=4, kv_blocks=5)
    eng2.submit(Request(0, np.zeros(10, np.int32), max_new_tokens=8))


def test_engine_paged_gating():
    cfg, params = _setup("llama3.2-1b")
    with pytest.raises(NotImplementedError):   # wave engine rebuilds caches
        GenerationEngine(params, cfg, batch_size=2, max_len=16,
                         mode="wave", kv_layout="paged")
    with pytest.raises(ValueError):
        GenerationEngine(params, cfg, batch_size=2, max_len=16,
                         kv_layout="banana")
    ssm_cfg, ssm_params = _setup("mamba2-130m")
    with pytest.raises(NotImplementedError):   # no attention cache to page
        GenerationEngine(ssm_params, ssm_cfg, batch_size=2, max_len=16,
                         mode="continuous", kv_layout="paged")


def test_kv_layout_env_defaults(monkeypatch):
    from repro.serving.engine import default_kv_block_size, default_kv_layout

    monkeypatch.delenv("ICQ_KV_LAYOUT", raising=False)
    monkeypatch.delenv("ICQ_KV_BLOCK_SIZE", raising=False)
    assert default_kv_layout() == "contiguous"
    assert default_kv_block_size() == 16
    monkeypatch.setenv("ICQ_KV_LAYOUT", "paged")
    assert default_kv_layout() == "paged"
    monkeypatch.setenv("ICQ_KV_LAYOUT", "rowwise")
    with pytest.raises(ValueError):
        default_kv_layout()
    monkeypatch.setenv("ICQ_KV_BLOCK_SIZE", "8")
    assert default_kv_block_size() == 8
    monkeypatch.setenv("ICQ_KV_BLOCK_SIZE", "0")
    with pytest.raises(ValueError):
        default_kv_block_size()
    monkeypatch.setenv("ICQ_KV_BLOCK_SIZE", "banana")
    with pytest.raises(ValueError):
        default_kv_block_size()


def test_engine_env_selects_paged(monkeypatch):
    cfg, params = _setup("llama3.2-1b")
    monkeypatch.setenv("ICQ_KV_LAYOUT", "paged")
    monkeypatch.setenv("ICQ_KV_BLOCK_SIZE", "4")
    eng = GenerationEngine(params, cfg, batch_size=2, max_len=16,
                           mode="continuous")
    assert eng.kv_layout == "paged" and eng.kv_block_size == 4
    # default pool = contiguous capacity in blocks
    assert eng.kv_blocks == 2 * (16 // 4)


# ---------------------------------------------------------------------------
# _paged_gather clamp contract: unmapped -1 entries read block 0, and
# nothing downstream may depend on what block 0 holds
# ---------------------------------------------------------------------------

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                                   # pragma: no cover
    HAVE_HYPOTHESIS = False


if HAVE_HYPOTHESIS:
    @settings(max_examples=25, deadline=None)
    @given(seed=st.integers(0, 2**31 - 1),
           nb=st.integers(2, 24),
           bs=st.integers(1, 8),
           lanes=st.integers(1, 4),
           width=st.integers(1, 6))
    def test_property_pool_refcount_conservation(seed, nb, bs, lanes,
                                                 width):
        """Any pool geometry, any share/fork/retain/evict schedule:
        refcounts stay exactly (page-table occurrences + external pins),
        free-list conservation holds every step, and full unpin + release
        returns every block."""
        rng = np.random.default_rng(seed)
        pool = KVBlockPool(num_blocks=nb, block_size=bs, n_lanes=lanes,
                           max_blocks_per_lane=width)
        _random_share_schedule(pool, rng, 120)

    @settings(max_examples=25, deadline=None)
    @given(seed=st.integers(0, 2**31 - 1),
           nb=st.integers(2, 24),
           bs=st.integers(1, 8),
           lanes=st.integers(1, 4),
           width=st.integers(1, 6),
           spec_k=st.integers(1, 6))
    def test_property_pool_trim_spec_schedule(seed, nb, bs, lanes, width,
                                              spec_k):
        """Any pool geometry, any draft/accept/reject schedule with
        rollback-by-trim over shared, forked and pinned chains: refcount
        and free-list conservation hold every step, a lane's position
        always stays backed, and full release returns every block."""
        rng = np.random.default_rng(seed)
        pool = KVBlockPool(num_blocks=nb, block_size=bs, n_lanes=lanes,
                           max_blocks_per_lane=width)
        _random_spec_schedule(pool, rng, 120, spec_k=spec_k)

    @settings(max_examples=30, deadline=None)
    @given(seed=st.integers(0, 2**31 - 1),
           nb=st.integers(1, 8),
           bs=st.integers(1, 4),
           B=st.integers(1, 3),
           n_pt=st.integers(1, 5))
    def test_property_paged_gather_clamps_to_block0(seed, nb, bs, B, n_pt):
        """Any page table over any pool: mapped entries gather their
        block bitwise, every unmapped (-1) entry gathers block 0 —
        that placeholder garbage is what the validity mask / in-kernel
        length mask must hide, so the clamp target is pinned here."""
        from repro.models.layers import _paged_gather

        rng = np.random.default_rng(seed)
        pool = rng.standard_normal((nb, 2, bs, 3)).astype(np.float32)
        pages = rng.integers(-1, nb, (B, n_pt)).astype(np.int32)
        out = np.asarray(_paged_gather(jnp.asarray(pool),
                                       jnp.asarray(pages)))
        assert out.shape == (B, n_pt * bs, 2, 3)
        view = out.reshape(B, n_pt, bs, 2, 3)
        for i in range(B):
            for j in range(n_pt):
                want = pool[max(int(pages[i, j]), 0)].swapaxes(0, 1)
                assert np.array_equal(view[i, j].view(np.uint8),
                                      want.view(np.uint8))


@pytest.mark.parametrize("arch", ["llama3.2-1b", "minicpm3-4b"])
def test_block0_garbage_never_reaches_logits(arch):
    """End-to-end clamp-contract probe: map every live page to blocks
    1..nb-1, then scramble block 0 of every pool leaf (the block all -1
    entries clamp to) with huge finite garbage — decode logits must be
    bitwise unchanged, on both the XLA gather arm and the Pallas
    in-kernel walk."""
    cfg, params = _setup(arch)
    B, L, S, bs = 2, 16, 4, 4
    n_pt = L // bs
    nb = 12
    rng = np.random.default_rng(7)
    plens = [7, 5]
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in plens]
    perm = rng.permutation(np.arange(1, nb))          # block 0 never mapped
    pages = np.full((B, n_pt), -1, np.int32)
    take = 0
    for i in range(B):
        need = -(-plens[i] // bs) + 1
        pages[i, :need] = perm[take: take + need]
        take += need
    d_pages = jnp.asarray(pages)

    cache = make_cache(params, cfg, B, L, per_lane=True, paged=(nb, bs))
    consumed = np.zeros(B, np.int32)
    for _ in range(2):
        lens = np.asarray([min(S, p - c) for p, c in zip(plens, consumed)],
                          np.int32).clip(0)
        toks = np.zeros((B, S), np.int32)
        for i in range(B):
            if lens[i]:
                toks[i, :lens[i]] = prompts[i][consumed[i]:
                                               consumed[i] + lens[i]]
        c = sync_cache_pages(
            sync_cache_positions(cache, jnp.asarray(consumed.copy())),
            d_pages)
        _, cache, _ = lm_apply(params, cfg, jnp.asarray(toks), cache=c,
                               start_pos=jnp.asarray(consumed.copy()),
                               seq_lens=jnp.asarray(lens))
        consumed += lens
    assert list(consumed) == plens

    def scramble_block0(cache):
        def leaf(name, v):
            if name == "index":
                return v
            return v.at[:, 0].set(jnp.full_like(v[:, 0], 1e9))
        attn = {k: leaf(k, v) for k, v in cache["stack"]["attn"].items()}
        return dict(cache, stack=dict(cache["stack"], attn=attn))

    nxt = rng.integers(0, cfg.vocab_size, (B, 1)).astype(np.int32)
    pos = np.asarray(plens, np.int32)

    def logits(cache, arm):
        import os
        old = os.environ.get("ICQ_PAGED_ATTN")
        os.environ["ICQ_PAGED_ATTN"] = arm
        try:
            c = sync_cache_pages(sync_cache_positions(
                cache, jnp.asarray(pos)), d_pages)
            return np.asarray(lm_apply(params, cfg, jnp.asarray(nxt),
                                       cache=c,
                                       start_pos=jnp.asarray(pos))[0])
        finally:
            if old is None:
                del os.environ["ICQ_PAGED_ATTN"]
            else:
                os.environ["ICQ_PAGED_ATTN"] = old

    poisoned = scramble_block0(cache)
    for arm in ("xla", "pallas"):
        clean = logits(cache, arm)
        dirty = logits(poisoned, arm)
        assert np.array_equal(clean.view(np.uint8), dirty.view(np.uint8)), (
            f"{arm}: block-0 garbage leaked into decode logits")
