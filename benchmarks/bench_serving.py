"""Serving-subsystem benchmark: continuous batching vs the legacy wave
engine under a Poisson arrival trace with mixed prompt/generation lengths.

One workload (requests, arrival times, prompt lengths, token budgets) is
replayed through both engine modes for each weight configuration —
``weight_cache='prepared'`` at runtime format v1 and v2, plus the
dequant-once ``'dense'`` cache. The wave engine idles finished lanes
until the slowest lane of each wave drains; the continuous engine
recycles a lane the step it finishes, so under mixed lengths it takes
fewer steps for the same tokens and aggregate tokens/s rises. Greedy
parity (continuous == wave token streams) is asserted per config.

A second **long-prompt trace** (prompts 64-256 tokens) replays the same
requests through the continuous engine at ``prefill_chunk=1`` (walk
every prompt token through the decode program, the pre-chunking
behavior) and ``prefill_chunk=PREFILL_CHUNK`` (drain prompt bulk
S-at-a-time through the chunk program / large-M kernel arm), asserting
greedy parity between both and against the wave engine, and reporting
the TTFT p50/p95 and aggregate tokens/s deltas chunking buys.

A third **paged-KV trace** (skewed lengths: a few long requests among
many short ones) replays one workload through ``kv_layout='contiguous'``
and ``kv_layout='paged'`` with the block pool sized *below* contiguous
capacity. It asserts greedy parity (preempt-and-requeue recomputes
identical streams), a strictly smaller cache footprint, sustained lane
occupancy, and that pool pressure actually exercised preemption —
reporting cache bytes, block utilization, preemption count and tokens/s
for both layouts. The paged workload additionally replays with the
fused mixed prefill/decode step disabled (``fused_step=False``, the
split chunk+decode structure), asserting greedy parity fused-vs-split
and strictly fewer device launches with fusion.

A fifth **multi-turn trace** (shared system prompt + 3-turn chats)
replays identical per-turn prompts through a warm engine
(``prefix_cache=True`` + sessions) and a cold one (plain paged prefill),
asserting per-turn greedy parity, that the prefix cache actually hit
(hit rate > 0, prefill tokens skipped > 0), and that warm turn-2+ TTFT
p50 improves by at least 2x — reporting the TTFT delta, tokens skipped
and the pool's cache-HBM ratio vs contiguous capacity.

A **speculative-decoding trace** (short prompts, big budgets: pure
decode-bound) replays one workload through the plain paged engine and
the speculative one (``spec_decode=True``, ngram drafter) plus the
adversarial always-wrong ``reject`` drafter. Token parity is asserted
for both spec runs **before** any speedup is reported; the row then
reports the decode tokens/s speedup (≥ 1.2x asserted), mean accepted
length, launch reduction and the reject worst case.

A fourth **fault-storm trace** replays the skewed workload through the
paged engine under a deterministic fault plan (NaN logits, a raised
launch, and an allocator-exhaustion drill) plus one request with
``max_queue_wait_s=0`` (deterministically ``expired``) and one cancelled
mid-decode from its own ``on_token`` callback. It asserts the
fault-tolerance contract: every submitted request terminates with a
typed status, every ``ok`` request's greedy stream is token-identical to
a no-fault run of the same workload, recovery actually engaged
(``degraded_steps >= 1``) and the engine shuts down with its pool and
scheduler invariants intact.

A sixth **replica-failover trace** runs the service layer itself: two
supervised ``EngineReplica`` workers behind a ``ReplicaRouter`` with a
WAL attached, one replica hard-killed mid-decode by a token-stream
chaos trigger. It asserts the service contract: every request
terminates exactly once with a typed status, failover actually engaged
(``failovers >= 1``, ``replica_restarts >= 1``,
``duplicate_terminals == 0``), every surviving greedy stream is
token-identical to a single-engine no-failure run, and the reopened
journal shows no pending requests.

Structured result lands in BENCH_serving.json via ``benchmarks/run.py``.
"""
from __future__ import annotations

import os
import tempfile
import time

import jax
import numpy as np

from benchmarks.common import emit
from repro.configs import get_config, smoke_variant
from repro.launch.quantize import quantize_tree
from repro.models import init_model
from repro.serving import (EngineReplica, GenerationEngine, ReplicaRouter,
                           Request, RequestWAL, ServiceMetrics)
from repro.serving.faults import FaultInjector, parse_fault_plan
from repro.serving.scheduler import STATUSES

ARCH = "llama3.2-1b"
BATCH = 4
MAX_LEN = 64
N_REQUESTS = 16
# Offered load must exceed service rate for continuous batching to have
# anything to win (a drained queue idles both engines equally): 200 Hz
# puts every arrival inside the first few decode steps on this host.
POISSON_RATE_HZ = 200.0
BITS = 3

# long-prompt trace: prompts of 64-256 tokens, where prefill dominates
# and the 1-token-per-step walk is the bottleneck chunking removes
PREFILL_CHUNK = 32
LONG_N_REQUESTS = 6
LONG_MAX_LEN = 288
LONG_MAX_NEW = (2, 9)
LONG_PROMPT = (64, 257)
# long-prompt chunking is benched on the configs where it matters most:
# prepared_v2 pays the per-launch XLA-arm overhead, so amortizing S
# tokens per launch is the headline win; dense is the
# weight-bandwidth-free control.
LONG_CONFIGS = ("prepared_v2", "dense")

# paged-KV trace: skewed lengths (a few long requests among many short
# ones) — the regime where reserving max_len contiguous rows for every
# lane wastes the most cache HBM. The paged pool is sized *below*
# contiguous capacity (PAGED_BLOCKS * PAGED_BLOCK_SIZE rows vs
# BATCH * PAGED_MAX_LEN), so the benchmark demonstrates the headline
# property: same lane occupancy and identical greedy streams at a
# strictly smaller cache footprint, with pool pressure absorbed by
# preempt-and-requeue instead of rejected admissions.
PAGED_BLOCK_SIZE = 8
PAGED_MAX_LEN = 96
PAGED_BLOCKS = 30          # 240 pooled rows < 4 * 96 = 384 contiguous
PAGED_N_REQUESTS = 12
PAGED_LONG_RIDS = (1, 3, 5)     # three long requests among the shorts
PAGED_PROMPT_LONG = (40, 57)
PAGED_NEW_LONG = (24, 33)
PAGED_PROMPT_SHORT = (2, 9)
PAGED_NEW_SHORT = (2, 9)
PAGED_PREFILL_CHUNK = 8         # exercise the paged chunk-write path
PAGED_CONFIGS = ("prepared_v2", "dense")

# multi-turn trace: MT_SESSIONS concurrent chats sharing one system
# prompt, MT_TURNS turns each. The warm engine retains each finished
# turn's chain under its session id (plus the hash cache for the
# cross-session system prompt), so turn 2+ only prefills the new user
# tokens; the cold engine re-prefills the whole history every turn.
# The pool is sized below contiguous capacity so the cache-HBM ratio
# is a real saving, with headroom for the retained session chains.
MT_SESSIONS = 3
MT_TURNS = 3
MT_SHARED = 32                  # shared system-prompt tokens: sized so
                                # cold re-pays several whole chunk
                                # launches per turn that warm skips —
                                # the 2x TTFT assertion must clear even
                                # on a noisy 2-core CI runner
MT_USER = (4, 9)                # fresh user tokens per turn
MT_MAX_NEW = (4, 7)
MT_MAX_LEN = 96
MT_BLOCK_SIZE = 4
MT_BLOCKS = 60                  # 240 pooled rows < 3 * 96 = 288 contiguous

# fault-storm trace: the skewed paged workload with one of each fault
# kind injected at fixed launch indices (all comfortably below the
# trace's launch count, so the whole plan fires), one request that can
# never be admitted in time, and one cancelled from its token stream.
FAULT_STORM_PLAN = "3:nan,7:raise,15:alloc"
FAULT_CANCEL_RID = 3            # a long request: cancelled mid-decode
FAULT_CANCEL_AFTER = 3          # ...after it has streamed this many tokens

# speculative-decoding trace: short prompts, big budgets — the pure
# decode-bound regime speculation targets. The weights are a briefly
# TRAINED checkpoint (not random init): speculation's payoff is
# acceptance, and acceptance needs a model whose greedy continuations
# are predictable — the serving regime (a converged LM on real text),
# not the wandering streams of random weights. One workload replays
# through the plain paged engine and the speculative one (ngram
# drafter: zero draft launches, so the speedup is purely
# verify-for-decode launch substitution); token parity is asserted
# BEFORE any speedup is reported. A third run with the adversarial
# always-wrong drafter pins the worst case: pure rejection overhead,
# parity still exact.
SPEC_TRAIN_STEPS = 120
SPEC_K = 4
SPEC_DRAFT = "ngram"
SPEC_N_REQUESTS = 8
SPEC_PROMPT = (2, 7)
SPEC_MAX_NEW = 40
SPEC_MAX_LEN = 64
SPEC_BLOCK_SIZE = 8
SPEC_BLOCKS = BATCH * SPEC_MAX_LEN // SPEC_BLOCK_SIZE
SPEC_MIN_SPEEDUP = 1.2

# replica-failover trace: the service layer (router + supervised
# replica workers + WAL) with one replica hard-killed mid-decode. Small
# on purpose — every replica engine (and each restart) pays a fresh
# jit compile, so the trace demonstrates the failover contract rather
# than throughput.
FAILOVER_REPLICAS = 2
FAILOVER_BATCH = 2
FAILOVER_N_REQUESTS = 6
FAILOVER_MAX_NEW = 6
FAILOVER_KILL_AFTER = 5         # streamed tokens before r0 is killed


def _workload(cfg, seed: int = 0):
    """Poisson arrivals, mixed prompt lengths (2-12) and budgets (2-32).

    The wide budget spread is the point: it is what makes the wave
    engine idle short lanes behind the longest lane of each wave (and
    what real traffic looks like). Sized so the step-count gap between
    the engines dwarfs per-step wall-clock noise on a shared host.
    """
    rng = np.random.default_rng(seed)
    arrivals = np.cumsum(rng.exponential(1.0 / POISSON_RATE_HZ, N_REQUESTS))
    specs = []
    for rid in range(N_REQUESTS):
        n_prompt = int(rng.integers(2, 13))
        specs.append(dict(
            rid=rid,
            prompt=rng.integers(0, cfg.vocab_size, n_prompt).astype(np.int32),
            max_new_tokens=int(rng.integers(2, 33)),
            arrival_time=float(arrivals[rid]),
        ))
    return specs


def _long_workload(cfg, seed: int = 1):
    """Poisson arrivals, long prompts (64-256), small budgets: TTFT is
    dominated by the prompt walk, the regime chunked prefill targets."""
    rng = np.random.default_rng(seed)
    arrivals = np.cumsum(
        rng.exponential(1.0 / POISSON_RATE_HZ, LONG_N_REQUESTS))
    return [dict(
        rid=rid,
        prompt=rng.integers(
            0, cfg.vocab_size, int(rng.integers(*LONG_PROMPT))
        ).astype(np.int32),
        max_new_tokens=int(rng.integers(*LONG_MAX_NEW)),
        arrival_time=float(arrivals[rid]),
    ) for rid in range(LONG_N_REQUESTS)]


def _skewed_workload(cfg, seed: int = 2):
    """Poisson arrivals, skewed lengths: a few long prompts with big
    budgets among many short ones — what makes per-lane max_len rows
    wasteful and a shared block pool dense."""
    rng = np.random.default_rng(seed)
    arrivals = np.cumsum(
        rng.exponential(1.0 / POISSON_RATE_HZ, PAGED_N_REQUESTS))
    specs = []
    for rid in range(PAGED_N_REQUESTS):
        long = rid in PAGED_LONG_RIDS
        p_lo, p_hi = PAGED_PROMPT_LONG if long else PAGED_PROMPT_SHORT
        n_lo, n_hi = PAGED_NEW_LONG if long else PAGED_NEW_SHORT
        specs.append(dict(
            rid=rid,
            prompt=rng.integers(
                0, cfg.vocab_size, int(rng.integers(p_lo, p_hi))
            ).astype(np.int32),
            max_new_tokens=int(rng.integers(n_lo, n_hi)),
            arrival_time=float(arrivals[rid]),
        ))
    return specs


def _run_engine(params, cfg, mode, weight_cache, fmt, specs,
                max_len=MAX_LEN, prefill_chunk=1, **engine_kw):
    engine = GenerationEngine(
        params, cfg, batch_size=BATCH, max_len=max_len,
        weight_cache=weight_cache, runtime_fmt=fmt, mode=mode,
        prefill_chunk=prefill_chunk, **engine_kw,
    )
    for s in specs:   # fresh Request objects: generated streams are mutable
        engine.submit(Request(**s))
    done = engine.run()
    summary = engine.metrics.summary()
    tokens = {rid: r.generated for rid, r in done.items()}
    return tokens, summary


def _run_fault_storm(params, cfg) -> dict:
    """No-fault baseline, then the storm: same workload + fault plan +
    an expired request + a mid-decode cancellation. Returns the bench
    row; raises AssertionError if the fault-tolerance contract breaks."""
    engine_kw = dict(
        batch_size=BATCH, max_len=PAGED_MAX_LEN, weight_cache="prepared",
        runtime_fmt="v2", mode="continuous",
        prefill_chunk=PAGED_PREFILL_CHUNK, kv_layout="paged",
        kv_block_size=PAGED_BLOCK_SIZE, kv_blocks=PAGED_BLOCKS,
    )
    specs = _skewed_workload(cfg)

    base_eng = GenerationEngine(params, cfg, **engine_kw)
    for s in specs:
        base_eng.submit(Request(**s))
    base = base_eng.run()
    base_eng.check_shutdown_invariants()
    base_tokens = {rid: r.generated for rid, r in base.items()}

    eng = GenerationEngine(
        params, cfg,
        faults=FaultInjector(parse_fault_plan(FAULT_STORM_PLAN)),
        **engine_kw)
    streamed = {"n": 0}

    def cancel_mid(rid, tok):
        streamed["n"] += 1
        if streamed["n"] == FAULT_CANCEL_AFTER:
            eng.cancel(rid)

    expired_rid = PAGED_N_REQUESTS
    last_arrival = specs[-1]["arrival_time"]
    for s in specs:
        kw = dict(s)
        if kw["rid"] == FAULT_CANCEL_RID:
            kw["on_token"] = cancel_mid
        eng.submit(Request(**kw))
    eng.submit(Request(
        expired_rid,
        np.arange(4, dtype=np.int32) % cfg.vocab_size,
        max_new_tokens=4, arrival_time=last_arrival,
        max_queue_wait_s=0.0))
    done = eng.run()
    eng.check_shutdown_invariants()
    summary = eng.metrics.summary()

    all_rids = {s["rid"] for s in specs} | {expired_rid}
    if set(done) != all_rids:
        raise AssertionError(
            f"fault_storm: requests lost ({sorted(all_rids - set(done))}) "
            f"or invented ({sorted(set(done) - all_rids)})")
    for rid, r in done.items():
        if r.status not in STATUSES:
            raise AssertionError(
                f"fault_storm: req {rid} ended without a typed status "
                f"({r.status!r})")
    if done[expired_rid].status != "expired":
        raise AssertionError(
            f"fault_storm: max_queue_wait_s=0 request ended "
            f"{done[expired_rid].status!r}, expected 'expired'")
    if done[FAULT_CANCEL_RID].status != "cancelled":
        raise AssertionError(
            f"fault_storm: cancelled request ended "
            f"{done[FAULT_CANCEL_RID].status!r}, expected 'cancelled'")
    # survivors must be bit-identical to the no-fault run: recovery that
    # changes tokens is corruption with extra steps
    mismatched = [
        rid for rid, r in done.items()
        if r.status == "ok" and r.generated != base_tokens[rid]
    ]
    if mismatched:
        raise AssertionError(
            f"fault_storm: ok-status streams diverged from the no-fault "
            f"run for rids {mismatched}")
    if summary["degraded_steps"] < 1:
        raise AssertionError(
            "fault_storm: recovery never engaged the degraded XLA arm")
    if eng.faults.pending:
        raise AssertionError(
            f"fault_storm: plan faults never drawn: {eng.faults.pending}")

    row = {k: (round(v, 4) if v == v else None) for k, v in summary.items()}
    row["status_counts"] = eng.metrics.status_counts()
    row["fault_kinds"] = dict(eng.metrics.faults)
    row["ok_parity"] = True
    return row


def _run_replica_failover(params, cfg) -> dict:
    """Single-engine no-failure baseline, then the same workload through
    two supervised replicas with r0 hard-killed mid-decode. Returns the
    bench row; raises AssertionError if the service contract breaks."""
    rng = np.random.default_rng(5)
    specs = [dict(
        rid=rid,
        prompt=rng.integers(
            0, cfg.vocab_size, int(rng.integers(4, 9))).astype(np.int32),
        max_new_tokens=FAILOVER_MAX_NEW,
    ) for rid in range(FAILOVER_N_REQUESTS)]

    def factory():
        return GenerationEngine(
            params, cfg, batch_size=FAILOVER_BATCH, max_len=MAX_LEN,
            weight_cache="prepared", runtime_fmt="v2", mode="continuous")

    base_eng = factory()
    for s in specs:
        base_eng.submit(Request(arrival_time=0.0, **s))
    base = base_eng.run()
    base_eng.check_shutdown_invariants()
    base_tokens = {rid: r.generated for rid, r in base.items()}

    metrics = ServiceMetrics()
    wal_path = os.path.join(
        tempfile.mkdtemp(prefix="icq-bench-wal-"), "requests.wal")
    wal = RequestWAL(wal_path)
    replicas = [EngineReplica(f"r{i}", factory, heartbeat_s=0.05)
                for i in range(FAILOVER_REPLICAS)]
    router = ReplicaRouter(replicas, wal=wal, metrics=metrics)
    chaos = {"streamed": 0, "killed": False}

    def kill_mid_decode(rid, tok):
        chaos["streamed"] += 1
        if chaos["streamed"] == FAILOVER_KILL_AFTER and not chaos["killed"]:
            chaos["killed"] = True
            router.kill("r0")

    router.token_observer = kill_mid_decode
    t0 = time.perf_counter()
    router.start()
    for s in specs:
        router.submit(Request(arrival_time=0.0, **s))
    give_up = time.monotonic() + 600.0
    while router.pending and time.monotonic() < give_up:
        router.supervise()
        time.sleep(0.02)
    router.supervise()
    wall = time.perf_counter() - t0
    done = router.results()
    router.stop()
    router.check_shutdown_invariants()
    wal.close()

    all_rids = {s["rid"] for s in specs}
    if set(done) != all_rids:
        raise AssertionError(
            f"replica_failover: requests lost "
            f"({sorted(all_rids - set(done))}) or invented "
            f"({sorted(set(done) - all_rids)})")
    if not chaos["killed"]:
        raise AssertionError(
            "replica_failover: chaos trigger never fired — the trace "
            "is not exercising the kill path")
    bad = {rid: st for rid, (st, _) in done.items() if st != "ok"}
    if bad:
        raise AssertionError(
            f"replica_failover: non-ok terminal statuses {bad}")
    # failover replays must continue the greedy streams token-exactly:
    # fold-into-prompt recovery that changes tokens is corruption
    mismatched = [rid for rid, (st, toks) in done.items()
                  if toks != base_tokens[rid]]
    if mismatched:
        raise AssertionError(
            f"replica_failover: ok-status streams diverged from the "
            f"no-failure run for rids {mismatched}")
    if metrics.failovers < 1 or metrics.replica_restarts < 1:
        raise AssertionError(
            f"replica_failover: kill did not engage recovery "
            f"(failovers={metrics.failovers}, "
            f"restarts={metrics.replica_restarts})")
    if metrics.duplicate_terminals:
        raise AssertionError(
            f"replica_failover: {metrics.duplicate_terminals} duplicate "
            f"terminal(s) — exactly-once broken")
    reopened = RequestWAL(wal_path)
    wal_pending_after = len(reopened.pending)
    wal_completed = len(reopened.completed)
    reopened.close()
    if wal_pending_after:
        raise AssertionError(
            f"replica_failover: reopened WAL still has "
            f"{wal_pending_after} pending request(s)")
    if set(reopened.completed) != all_rids:
        raise AssertionError(
            "replica_failover: WAL terminal records do not cover the "
            "workload")

    row = {k: (round(v, 4) if v == v else None)
           for k, v in metrics.summary().items()}
    row.update(
        wall_s=round(wall, 4), requests=FAILOVER_N_REQUESTS,
        replicas=FAILOVER_REPLICAS, kill_after=FAILOVER_KILL_AFTER,
        status_counts=dict(metrics.status_counts),
        ok_parity=True, wal_pending_after=wal_pending_after,
        wal_completed=wal_completed,
    )
    return row


def _run_spec_decode(cfg) -> dict:
    """Plain vs speculative paged serving on one decode-bound workload.
    Token parity is asserted before any number is reported — a speedup
    over diverging streams is not a speedup. Returns the bench row;
    raises AssertionError on parity loss or a sub-threshold speedup."""
    from repro.launch.train import train

    tparams, _ = train(ARCH, steps=SPEC_TRAIN_STEPS, batch=8, seq=64,
                       ckpt_dir=tempfile.mkdtemp(prefix="icq-bench-spec-"),
                       log_every=SPEC_TRAIN_STEPS)
    params, _ = quantize_tree(tparams, BITS, gamma=0.05)
    rng = np.random.default_rng(11)
    specs = [dict(
        rid=rid,
        prompt=rng.integers(
            0, cfg.vocab_size, int(rng.integers(*SPEC_PROMPT))
        ).astype(np.int32),
        max_new_tokens=SPEC_MAX_NEW,
        arrival_time=0.0,
    ) for rid in range(SPEC_N_REQUESTS)]
    engine_kw = dict(
        batch_size=BATCH, max_len=SPEC_MAX_LEN, weight_cache="prepared",
        runtime_fmt="v2", mode="continuous", kv_layout="paged",
        kv_block_size=SPEC_BLOCK_SIZE, kv_blocks=SPEC_BLOCKS,
    )

    def one(label, **extra):
        # jit caches are per-engine (each engine closes over its own
        # step programs), so steady state is measured by a warm-up run
        # of the SAME workload through the SAME engine first — the
        # measured pass then pays launches, not compiles
        eng = GenerationEngine(params, cfg, **engine_kw, **extra)
        for s in specs:
            eng.submit(Request(**s))
        eng.run()
        before = eng.metrics.summary()
        for s in specs:
            eng.submit(Request(rid=s["rid"] + 100, prompt=s["prompt"].copy(),
                               max_new_tokens=s["max_new_tokens"],
                               arrival_time=0.0))
        t0 = time.perf_counter()
        done = eng.run()
        wall = time.perf_counter() - t0
        eng.check_shutdown_invariants()
        tokens = {rid - 100: r.generated for rid, r in done.items()
                  if rid >= 100}
        summary = eng.metrics.summary()
        n_tok = sum(len(g) for g in tokens.values())
        summary["wall_s"] = wall
        summary["tokens_per_s"] = n_tok / wall
        summary["launches"] -= before["launches"]
        return tokens, summary

    tok_p, sum_p = one("plain")
    tok_s, sum_s = one("spec", spec_decode=True, spec_k=SPEC_K,
                       spec_draft=SPEC_DRAFT)
    tok_r, sum_r = one("reject", spec_decode=True, spec_k=SPEC_K,
                       spec_draft="reject")

    # parity gate: no speedup is reported unless every stream matches
    if tok_s != tok_p:
        raise AssertionError(
            "spec_decode: speculative streams diverged from plain decode "
            f"on rids {[r for r in tok_p if tok_s.get(r) != tok_p[r]]}")
    if tok_r != tok_p:
        raise AssertionError(
            "spec_decode: reject-drafter streams diverged from plain "
            "decode — the rejection/rollback path corrupts state")

    speedup = sum_s["tokens_per_s"] / sum_p["tokens_per_s"]
    if speedup < SPEC_MIN_SPEEDUP:
        raise AssertionError(
            f"spec_decode: {speedup:.2f}x below the {SPEC_MIN_SPEEDUP}x "
            f"decode tokens/s target (mean accept "
            f"{sum_s['mean_accept_len']:.2f} of k={SPEC_K})")

    def _round(s):
        return {k: (round(v, 4) if v == v else None) for k, v in s.items()}

    return dict(
        requests=SPEC_N_REQUESTS, max_new=SPEC_MAX_NEW, spec_k=SPEC_K,
        draft=SPEC_DRAFT, max_len=SPEC_MAX_LEN,
        train_steps=SPEC_TRAIN_STEPS,
        plain=_round(sum_p), spec=_round(sum_s), reject=_round(sum_r),
        token_parity=True,
        speedup_tokens_per_s=round(speedup, 3),
        reject_slowdown_tokens_per_s=round(
            sum_r["tokens_per_s"] / sum_p["tokens_per_s"], 3),
        mean_accept_len=round(sum_s["mean_accept_len"], 3),
        accept_rate=round(sum_s["spec_accept_rate"], 3),
        launch_reduction=round(sum_p["launches"] / sum_s["launches"], 3),
    )


def _run_multi_turn(params, cfg) -> dict:
    """Warm (prefix cache + sessions) vs cold multi-turn serving on
    identical per-turn prompts. Returns the bench row; raises
    AssertionError if parity breaks or the cache fails to pay off."""
    engine_kw = dict(
        batch_size=MT_SESSIONS, max_len=MT_MAX_LEN,
        weight_cache="prepared", runtime_fmt="v2", mode="continuous",
        prefill_chunk=PAGED_PREFILL_CHUNK, kv_layout="paged",
        kv_block_size=MT_BLOCK_SIZE, kv_blocks=MT_BLOCKS,
    )
    warm = GenerationEngine(params, cfg, prefix_cache=True, **engine_kw)
    cold = GenerationEngine(params, cfg, prefix_cache=False, **engine_kw)

    rng = np.random.default_rng(7)
    # compile warm-up: one throwaway 2-turn session through each engine
    # so the jit compiles (chunk / decode / fused programs plus the COW
    # fork row-copy, which only triggers on a mid-block warm start) land
    # outside the measured TTFTs. The warm cache is cleared afterwards;
    # only the counter ledger keeps the warm-up's few lookups.
    wh = rng.integers(0, cfg.vocab_size, 8).astype(np.int32)
    for wturn in range(2):
        wuser = rng.integers(0, cfg.vocab_size, 4).astype(np.int32)
        wprompt = np.concatenate([wh, wuser])
        wrid = 9000 + wturn
        warm.submit(Request(wrid, wprompt.copy(), max_new_tokens=2,
                            arrival_time=warm.now()), session="warmup")
        dw = warm.run()
        cold.submit(Request(wrid, wprompt.copy(), max_new_tokens=2,
                            arrival_time=cold.now()))
        cold.run()
        wh = np.concatenate(
            [wprompt, np.asarray(dw[wrid].generated, np.int32)])
    warm.clear_prefix_cache()

    system = rng.integers(0, cfg.vocab_size, MT_SHARED).astype(np.int32)
    history = {sid: system.copy() for sid in range(MT_SESSIONS)}
    ttfts = {"warm": [[] for _ in range(MT_TURNS)],
             "cold": [[] for _ in range(MT_TURNS)]}
    rid = 0
    for turn in range(MT_TURNS):
        turn_reqs = []
        for sid in range(MT_SESSIONS):
            user = rng.integers(
                0, cfg.vocab_size, int(rng.integers(*MT_USER))
            ).astype(np.int32)
            prompt = np.concatenate([history[sid], user])
            max_new = int(rng.integers(*MT_MAX_NEW))
            turn_reqs.append((rid, sid, prompt, max_new))
            rid += 1
        # each engine is submitted-then-run by itself: arrival stamps
        # come from its own clock right before its run, so neither
        # engine's TTFT absorbs the other's wall time
        for r, sid, prompt, max_new in turn_reqs:
            warm.submit(Request(r, prompt.copy(), max_new_tokens=max_new,
                                arrival_time=warm.now()),
                        session=f"s{sid}")
        done_w = warm.run()
        for r, sid, prompt, max_new in turn_reqs:
            cold.submit(Request(r, prompt.copy(), max_new_tokens=max_new,
                                arrival_time=cold.now()))
        done_c = cold.run()
        for r, sid, prompt, _ in turn_reqs:
            if done_w[r].generated != done_c[r].generated:
                raise AssertionError(
                    f"multi_turn: warm vs cold greedy streams diverge on "
                    f"session {sid} turn {turn} "
                    f"({done_w[r].generated} vs {done_c[r].generated})")
            history[sid] = np.concatenate(
                [prompt, np.asarray(done_w[r].generated, np.int32)])
            ttfts["warm"][turn].append(warm.metrics.requests[r].ttft)
            ttfts["cold"][turn].append(cold.metrics.requests[r].ttft)
    warm.check_shutdown_invariants()
    cold.check_shutdown_invariants()

    sw = warm.metrics.summary()
    sc = cold.metrics.summary()
    if not sw["prefix_hit_rate"] > 0:
        raise AssertionError("multi_turn: warm engine never hit the "
                             "prefix cache")
    if not sw["prefix_tokens_skipped"] > 0:
        raise AssertionError("multi_turn: warm engine skipped no prefill "
                             "tokens")
    if sw["session_hits"] < 1:
        raise AssertionError("multi_turn: no turn warm-started from a "
                             "retained session chain")
    # the headline claim: once a session's history is resident, TTFT is
    # the delta prefill, not the whole history — p50 over turn-2+
    # requests must improve at least 2x
    late_w = sorted(t for turn in ttfts["warm"][1:] for t in turn)
    late_c = sorted(t for turn in ttfts["cold"][1:] for t in turn)
    warm_p50 = late_w[len(late_w) // 2]
    cold_p50 = late_c[len(late_c) // 2]
    if not warm_p50 * 2 <= cold_p50:
        raise AssertionError(
            f"multi_turn: warm turn-2+ TTFT p50 {warm_p50:.4f}s not 2x "
            f"better than cold {cold_p50:.4f}s")

    contiguous_rows = MT_SESSIONS * MT_MAX_LEN
    paged_rows = MT_BLOCKS * MT_BLOCK_SIZE
    row = dict(
        sessions=MT_SESSIONS, turns=MT_TURNS, shared_prefix=MT_SHARED,
        block_size=MT_BLOCK_SIZE, kv_blocks=MT_BLOCKS,
        warm={k: (round(v, 4) if v == v else None) for k, v in sw.items()},
        cold={k: (round(v, 4) if v == v else None) for k, v in sc.items()},
        greedy_parity=True,
        ttft_p50_turn2plus_warm_s=round(warm_p50, 4),
        ttft_p50_turn2plus_cold_s=round(cold_p50, 4),
        ttft_speedup_turn2plus=round(cold_p50 / warm_p50, 3),
        prefill_tokens_skipped=int(sw["prefix_tokens_skipped"]),
        cache_hbm_ratio=round(paged_rows / contiguous_rows, 3),
    )
    return row


# trace names accepted by ``run(traces=...)`` and the ``--trace`` CLI flag
TRACES = ("short", "long_prompt", "paged_kv", "multi_turn",
          "spec_decode", "fault_storm", "replica_failover")


def run(traces=None) -> dict:
    """Run the serving benchmark traces; ``traces`` optionally restricts
    the run to a subset of the names in ``TRACES`` (default: all)."""
    want = set(TRACES if traces is None else traces)
    unknown = want - set(TRACES)
    if unknown:
        raise ValueError(
            f"unknown traces {sorted(unknown)}; available: {list(TRACES)}")
    cfg = smoke_variant(get_config(ARCH))
    params = init_model(jax.random.PRNGKey(0), cfg)
    qparams, acct = quantize_tree(params, BITS, gamma=0.05)
    specs = _workload(cfg)

    out = dict(
        arch=ARCH, batch=BATCH, max_len=MAX_LEN, requests=N_REQUESTS,
        poisson_rate_hz=POISSON_RATE_HZ, bits=BITS,
        mean_bits=round(acct["mean_bits"], 3),
        by_config={},
    )
    configs = (
        ("prepared_v1", qparams, "prepared", "v1"),
        ("prepared_v2", qparams, "prepared", "v2"),
        ("dense", qparams, "dense", None),
    )
    if "short" in want:
        for tag, p, wc, fmt in configs:
            row = {}
            tokens = {}
            for mode in ("wave", "continuous"):
                tokens[mode], summary = _run_engine(p, cfg, mode, wc, fmt, specs)
                row[mode] = {
                    k: (round(v, 4) if v == v else None)  # NaN -> null
                    for k, v in summary.items()
                }
            row["speedup_tokens_per_s"] = round(
                row["continuous"]["tokens_per_s"] / row["wave"]["tokens_per_s"], 3)
            row["greedy_parity"] = tokens["continuous"] == tokens["wave"]
            if not row["greedy_parity"]:   # a speedup over diverging token
                raise AssertionError(      # streams is not a speedup
                    f"{tag}: continuous vs wave greedy token streams diverge")
            out["by_config"][tag] = row
            emit(
                f"serving/{tag}_continuous",
                row["continuous"]["wall_s"] * 1e6,
                f"tok_s={row['continuous']['tokens_per_s']};"
                f"wave_tok_s={row['wave']['tokens_per_s']};"
                f"speedup={row['speedup_tokens_per_s']}x;"
                f"parity={row['greedy_parity']};"
                f"occupancy={row['continuous']['mean_occupancy']}"
                f"vs{row['wave']['mean_occupancy']}",
            )

    if "long_prompt" in want:
        # ---- long-prompt trace: chunked vs unchunked prefill --------------
        long_specs = _long_workload(cfg)
        out["long_prompt"] = dict(
            requests=LONG_N_REQUESTS, max_len=LONG_MAX_LEN,
            prompt_range=list(LONG_PROMPT), prefill_chunk=PREFILL_CHUNK,
            by_config={},
        )
        for tag, p, wc, fmt in configs:
            if tag not in LONG_CONFIGS:
                continue
            tokens = {}
            row = {}
            runs = (
                ("wave", dict(mode="wave")),
                ("chunk1", dict(mode="continuous", prefill_chunk=1)),
                ("chunked", dict(mode="continuous",
                                 prefill_chunk=PREFILL_CHUNK)),
            )
            for label, kw in runs:
                tokens[label], summary = _run_engine(
                    p, cfg, weight_cache=wc, fmt=fmt, specs=long_specs,
                    max_len=LONG_MAX_LEN, **kw)
                row[label] = {
                    k: (round(v, 4) if v == v else None)  # NaN -> null
                    for k, v in summary.items()
                }
            # greedy continuous output must stay token-identical to wave per
            # request with chunking enabled — a TTFT win over diverging
            # streams is not a win.
            row["greedy_parity"] = (
                tokens["chunked"] == tokens["chunk1"] == tokens["wave"])
            if not row["greedy_parity"]:
                raise AssertionError(
                    f"{tag}: chunked prefill token streams diverge "
                    f"(chunked vs chunk1 vs wave)")
            row["speedup_tokens_per_s"] = round(
                row["chunked"]["tokens_per_s"] / row["chunk1"]["tokens_per_s"],
                3)
            row["ttft_p50_delta_s"] = round(
                row["chunk1"]["ttft_p50"] - row["chunked"]["ttft_p50"], 4)
            row["ttft_p95_delta_s"] = round(
                row["chunk1"]["ttft_p95"] - row["chunked"]["ttft_p95"], 4)
            out["long_prompt"]["by_config"][tag] = row
            emit(
                f"serving/long_prompt_{tag}_chunk{PREFILL_CHUNK}",
                row["chunked"]["wall_s"] * 1e6,
                f"tok_s={row['chunked']['tokens_per_s']};"
                f"chunk1_tok_s={row['chunk1']['tokens_per_s']};"
                f"speedup={row['speedup_tokens_per_s']}x;"
                f"ttft_p95={row['chunked']['ttft_p95']}"
                f"vs{row['chunk1']['ttft_p95']};"
                f"parity={row['greedy_parity']};"
                f"prefill_tokens={row['chunked']['prefill_tokens']}",
            )

    if "paged_kv" in want:
        # ---- paged-KV trace: block pool vs contiguous rows ----------------
        paged_specs = _skewed_workload(cfg)
        out["paged_kv"] = dict(
            requests=PAGED_N_REQUESTS, max_len=PAGED_MAX_LEN,
            block_size=PAGED_BLOCK_SIZE, kv_blocks=PAGED_BLOCKS,
            prefill_chunk=PAGED_PREFILL_CHUNK,
            contiguous_rows=BATCH * PAGED_MAX_LEN,
            paged_rows=PAGED_BLOCKS * PAGED_BLOCK_SIZE,
            by_config={},
        )
        for tag, p, wc, fmt in configs:
            if tag not in PAGED_CONFIGS:
                continue
            tokens = {}
            row = {}
            runs = (
                ("contiguous", dict(kv_layout="contiguous")),
                ("paged", dict(kv_layout="paged",
                               kv_block_size=PAGED_BLOCK_SIZE,
                               kv_blocks=PAGED_BLOCKS)),
                # split two-launch structure: the fused-step control
                ("paged_split", dict(kv_layout="paged",
                                     kv_block_size=PAGED_BLOCK_SIZE,
                                     kv_blocks=PAGED_BLOCKS,
                                     fused_step=False)),
            )
            for label, kw in runs:
                tokens[label], summary = _run_engine(
                    p, cfg, mode="continuous", weight_cache=wc, fmt=fmt,
                    specs=paged_specs, max_len=PAGED_MAX_LEN,
                    prefill_chunk=PAGED_PREFILL_CHUNK, **kw)
                row[label] = {
                    k: (round(v, 4) if v == v else None)  # NaN -> null
                    for k, v in summary.items()
                }
            # identical greedy streams at a strictly smaller footprint is the
            # whole claim — preemption replays must recompute exact tokens,
            # and folding mixed iterations into one fused launch must not
            # change a single token either.
            row["greedy_parity"] = (tokens["paged"] == tokens["contiguous"]
                                    == tokens["paged_split"])
            if not row["greedy_parity"]:
                raise AssertionError(
                    f"{tag}: paged / contiguous / split-step greedy token "
                    f"streams diverge")
            # fused mixed iterations are ONE launch: strictly fewer device
            # launches than the split chunk+decode structure for the same
            # tokens
            fused_l = row["paged"]["launches"]
            split_l = row["paged_split"]["launches"]
            row["launch_reduction"] = round(split_l / fused_l, 3)
            if not (row["paged"]["fused_steps"] >= 1 and fused_l < split_l):
                raise AssertionError(
                    f"{tag}: fused step did not reduce launches "
                    f"({fused_l} fused vs {split_l} split)")
            c_bytes = row["contiguous"]["cache_bytes"]
            p_bytes = row["paged"]["cache_bytes"]
            row["cache_bytes_ratio"] = round(p_bytes / c_bytes, 3)
            if not p_bytes < c_bytes:
                raise AssertionError(
                    f"{tag}: paged cache ({p_bytes} B) not smaller than "
                    f"contiguous ({c_bytes} B)")
            occ_c = row["contiguous"]["mean_occupancy"]
            occ_p = row["paged"]["mean_occupancy"]
            row["occupancy_ratio"] = round(occ_p / occ_c, 3)
            # the smaller pool must not cost served concurrency: paged lanes
            # stay as full as contiguous ones (measured ratio 0.98-1.00 on
            # this host; 5% slack absorbs step-count jitter from
            # wall-clock-dependent admission timing on shared CI runners)
            if not occ_p >= 0.95 * occ_c:
                raise AssertionError(
                    f"{tag}: paged occupancy {occ_p} fell below contiguous "
                    f"{occ_c}")
            if row["paged"]["preemptions"] < 1:
                raise AssertionError(
                    f"{tag}: pool pressure never triggered a preemption — "
                    f"the trace is not exercising the requeue path")
            out["paged_kv"]["by_config"][tag] = row
            emit(
                f"serving/paged_kv_{tag}",
                row["paged"]["wall_s"] * 1e6,
                f"tok_s={row['paged']['tokens_per_s']}"
                f"vs{row['contiguous']['tokens_per_s']};"
                f"cache_bytes={int(p_bytes)}vs{int(c_bytes)};"
                f"occupancy={occ_p}vs{occ_c};"
                f"preemptions={int(row['paged']['preemptions'])};"
                f"block_util={row['paged']['mean_block_utilization']};"
                f"launches={int(fused_l)}vs{int(split_l)};"
                f"parity={row['greedy_parity']}",
            )

    if "multi_turn" in want:
        # ---- multi-turn trace: warm sessions vs cold re-prefill -----------
        mt = _run_multi_turn(qparams, cfg)
        out["multi_turn"] = mt
        emit(
            "serving/multi_turn_warm",
            mt["warm"]["wall_s"] * 1e6,
            f"ttft_p50_turn2plus={mt['ttft_p50_turn2plus_warm_s']}"
            f"vs{mt['ttft_p50_turn2plus_cold_s']};"
            f"speedup={mt['ttft_speedup_turn2plus']}x;"
            f"hit_rate={mt['warm']['prefix_hit_rate']};"
            f"tokens_skipped={mt['prefill_tokens_skipped']};"
            f"cow_forks={int(mt['warm']['cow_forks'])};"
            f"cache_hbm_ratio={mt['cache_hbm_ratio']};"
            f"parity={mt['greedy_parity']}",
        )

    if "spec_decode" in want:
        # ---- speculative-decoding trace: draft-and-verify vs plain --------
        sd = _run_spec_decode(cfg)
        out["spec_decode"] = sd
        emit(
            "serving/spec_decode",
            sd["spec"]["wall_s"] * 1e6,
            f"tok_s={sd['spec']['tokens_per_s']}"
            f"vs{sd['plain']['tokens_per_s']};"
            f"speedup={sd['speedup_tokens_per_s']}x;"
            f"mean_accept_len={sd['mean_accept_len']}of{SPEC_K};"
            f"accept_rate={sd['accept_rate']};"
            f"launches={int(sd['spec']['launches'])}"
            f"vs{int(sd['plain']['launches'])};"
            f"reject_worst_case={sd['reject_slowdown_tokens_per_s']}x;"
            f"parity={sd['token_parity']}",
        )

    if "fault_storm" in want:
        # ---- fault-storm trace: typed termination + recovery parity -------
        storm = _run_fault_storm(qparams, cfg)
        out["fault_storm"] = dict(
            plan=FAULT_STORM_PLAN, cancel_rid=FAULT_CANCEL_RID,
            expired_rid=PAGED_N_REQUESTS, row=storm,
        )
        emit(
            "serving/fault_storm",
            storm["wall_s"] * 1e6,
            f"statuses={storm['status_counts']};"
            f"faults={storm['fault_kinds']};"
            f"degraded_steps={int(storm['degraded_steps'])};"
            f"replays={int(storm['replays'])};"
            f"ok_parity={storm['ok_parity']}",
        )

    if "replica_failover" in want:
        # ---- replica-failover trace: router + supervised replicas ---------
        fo = _run_replica_failover(qparams, cfg)
        out["replica_failover"] = dict(
            replicas=FAILOVER_REPLICAS, requests=FAILOVER_N_REQUESTS,
            kill_after=FAILOVER_KILL_AFTER, row=fo,
        )
        emit(
            "serving/replica_failover",
            fo["wall_s"] * 1e6,
            f"failovers={int(fo['failovers'])};"
            f"restarts={int(fo['replica_restarts'])};"
            f"kills={int(fo['replica_kills'])};"
            f"dup_terminals={int(fo['duplicate_terminals'])};"
            f"statuses={fo['status_counts']};"
            f"ok_parity={fo['ok_parity']};"
            f"wal_pending_after={fo['wal_pending_after']}",
        )
    return out


if __name__ == "__main__":
    import argparse
    import json

    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--trace", action="append", choices=list(TRACES), default=None,
        help="run only the named trace(s) (repeatable); default: all. "
        "The selected subset still lands in BENCH_serving.json.")
    args = ap.parse_args()
    result = run(args.trace)
    with open("BENCH_serving.json", "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps(result, indent=1))
