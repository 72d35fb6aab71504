"""Serving engine: continuous batching over a slot-based KV cache.

The engine runs a **single persistent jitted step** — decode one token
for every lane, then sample — over a per-lane-position KV cache
(``make_cache(..., per_lane=True)``). A slot scheduler
(serving/scheduler.py) owns admission: requests queue with arrival
times, a free slot is filled the same step the previous occupant emits
EOS (lane recycling), and dead slots are masked so their logits are
never sampled. Per-lane positions mean one lane can be at position 3 of
its prompt while its neighbor is 40 tokens into generation — there is
no wave barrier, which is what converts the ICQ kernels' bandwidth win
into aggregate served tokens/s under mixed-length traffic.

Prompt handling has two gears. With ``prefill_chunk=1`` (the default)
prompts are walked one token per step in the same jitted program as
generation (teacher forcing: lanes inside their prompt feed the next
prompt token and ignore the sampled one) — no second program runs.
With ``prefill_chunk=S > 1`` a second persistent jitted program
(``launch/steps.make_prefill_chunk_step``) drains newly admitted
prompts S tokens at a time: every lane with bulk prompt left consumes
``min(S, remaining)`` tokens per launch (ragged tails and mid-decode
lanes are write-masked via per-lane ``seq_lens``, never re-padded or
re-traced), which routes the prompt matmuls through the large-M
dequant+MXU dispatch arm instead of paying one full decode step per
prompt token. The chunk program never samples: the first generated
token's logits always come from the decode step consuming the last
prompt token, so chunking changes *when* cache rows are written but
never what any sampled token sees — greedy continuous output stays
token-identical to the wave engine (and to ``prefill_chunk=1``).
Exactness caveat: that identity is bitwise when chunk and decode
matmuls execute the same math (the pure-XLA arm, or any same-arm
configuration — what CI pins); on the Pallas backend the chunk step's
M = B*S lands on the dequant+MXU arm while the 1-token walk's M = B
rides the fused kernel, whose different K-reduction order can differ in
the last ulp — the compiled-TPU validation pass (ROADMAP) owns
re-checking greedy stability there. ``ICQ_PREFILL_CHUNK`` sets the
default chunk.

On top of chunking, the **fused step** (``fused_step=True`` whenever
chunking is active; ``ICQ_FUSED_STEP=0`` restores the split structure)
folds the decode token into the chunk program's token axis: a mixed
prefill+decode iteration — some lanes admitting bulk prompt, others
generating — runs as ONE device launch
(``launch/steps.make_fused_step``) instead of a chunk pass followed by
a decode pass. Each lane consumes ``min(S, prompt remaining)`` tokens
(including its final prompt token) or exactly its decode token, and
sampling happens in the same launch from each lane's own last valid
column. Once every live lane is a decode lane the engine falls back to
the plain 1-token decode program, so pure-decode steady state is
untouched. Greedy fused output is token-identical to the split
structure (same same-arm caveat as chunking; CI pins it); sampled
streams differ because the fused engine draws one PRNG subkey per
iteration where the split engine draws none on chunk-only iterations.
Sampling
(serving/sampling.py) is fused into the decode step: greedy by
default, per-request temperature / top-k / top-p overrides, PRNG key
threaded from the engine seed.

``kv_layout`` selects how the continuous engine's KV cache charges HBM:

  * 'contiguous' (default; ``ICQ_KV_LAYOUT`` overrides) — every lane
    owns ``max_len`` cache rows up front: bit-for-bit the pre-paging
    engine. Cache HBM = ``batch * max_len`` rows regardless of traffic.
  * 'paged' — vLLM-style block pool (serving/kv_pool.py): cache rows
    live in ``kv_blocks`` physical blocks of ``kv_block_size`` rows;
    lanes map logical positions through per-lane page tables, appending
    a block only when their position crosses a block boundary and
    giving every block back the step they finish. Admission becomes
    allocator-aware (a request is only admitted when free blocks cover
    its prompt plus a minimum decode budget) and pool exhaustion
    preempts the youngest lane — its request requeues at the queue
    head with generated tokens folded into the prompt, so a greedy
    stream is *recomputed identically* after preemption. Greedy output
    is token-identical to 'contiguous' (CI-pinned); only HBM footprint
    and scheduling change. Cache HBM = ``kv_blocks * kv_block_size``
    rows — decoupled from ``batch * max_len``, which is what converts
    ICQuant's weight savings into concurrent-lane headroom.

``mode`` selects the runtime:

  * 'continuous' — the slot engine above. Dense / moe / vlm families
    run it natively; SSM and hybrid mixers run it via per-lane *state
    reset* (a (B,) reset mask threads into ``mamba2_apply`` and zeroes
    a recycled lane's conv/ssm state slices the step it is admitted —
    recurrent state has no positions to rewind, but zeroing on admit is
    exactly the fresh-cache semantics the wave engine provides).
    Enc-dec models and sliding-window ring caches stay wave-only.
  * 'wave'       — the legacy wave-synchronous static batcher kept as
    the parity baseline: admit up to ``batch_size`` requests, step every
    lane from position 0 until the *slowest* lane finishes, then admit
    the next wave with a fresh cache. Greedy only.
  * 'auto' (default) — 'continuous' when the config supports it, else
    'wave'.

With greedy sampling both modes emit token-identical streams for the
same request set (lanes are batch-independent; the parity test in CI
pins this), so 'auto' never changes results — only scheduling.

Quantized weights are converted ONCE at engine construction
(``weight_cache='prepared'``, the default): ICQPacked storage weights
become pre-padded ICQPrepared layouts, so the per-step jitted program
routes every matmul through the kernel-backed dispatch layer
(kernels/backend.py). ``runtime_fmt`` picks the prepared runtime format
(None = platform default, normally 'v2' — the checkpointed gap-stream
layout serving at ~0.3-0.45 b/w outlier overhead); ``'dense'``
materializes dense weights once; ``'none'`` keeps the reference
in-graph decode. A MetricsCollector (serving/metrics.py) records TTFT,
queue wait, tokens/s, slot occupancy and queue depth for every run.

Fault tolerance (this layer's additions; every default preserves the
pre-fault-tolerance engine bit-for-bit):

  * **Request lifecycle** — ``Request.deadline_s`` (end-to-end, from
    arrival on the engine clock) and ``max_queue_wait_s`` (queue wait
    alone) are enforced once per engine iteration: an expired running
    lane finishes with status ``'timeout'`` (partial output kept), an
    expired queued request with ``'expired'``. ``cancel(rid)`` is safe
    from ``on_token`` callbacks: a queued request leaves the queue, a
    running lane is torn down (slot + paged blocks freed) at the next
    iteration boundary, both with status ``'cancelled'``. Every request
    handed back by ``run()`` carries exactly one terminal
    ``Request.status`` from ``scheduler.STATUSES``.
  * **Backpressure** — ``max_queue`` bounds the submit queue
    (``ICQ_MAX_QUEUE``; None = unbounded, the historical behavior).
    ``submit`` returns False for a request the ``shed_policy``
    (``ICQ_SHED_POLICY``) turned away: ``'reject'`` sheds the *new*
    request, ``'shed-oldest'`` sheds the longest-queued one and admits
    the new. Shed requests terminate with status ``'rejected'``.
  * **Fault injection + recovery** — a seeded ``FaultInjector``
    (serving/faults.py; ``ICQ_FAULT_PLAN`` / ``ICQ_FAULT_RATE`` /
    ``ICQ_FAULT_SEED``) can fail chosen launches. Injected or not,
    every step launch is *checked*: launches that raise, and decode
    launches whose logits come back NaN/inf on a live lane (the
    signature of a corrupted v2 gap stream), are retried **once, on the
    bitwise-exact pure-XLA arm** (``kernels/backend.forced_backend``) —
    degraded mode, which then stays sticky for ``degrade_steps`` clean
    launches (``ICQ_DEGRADE_STEPS``, default 8) before dispatch returns
    to the kernel arms. If the degraded retry also fails, the engine
    falls back to the paged engine's preempt-and-requeue machinery:
    every live lane is preempted and replayed (greedy streams recompute
    identically). A request that needs more than two replays — a
    genuinely poisoned weight would otherwise loop forever — finishes
    as ``'failed'``, as does a sampled (temperature > 0) preemption
    victim, whose replay would silently diverge. The metrics ledger
    (faults / degraded_steps / replays / timeouts / cancellations /
    sheds) makes every recovery visible.
"""
from __future__ import annotations

import contextlib
import os
import time
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.kernels.backend import forced_backend
from repro.launch.steps import fork_cache_block, make_cache, \
    make_decode_step, make_fused_step, make_prefill_chunk_step, \
    prepare_serving_params
from repro.serving.faults import FaultInjected, FaultInjector
from repro.serving.kv_pool import KVBlockPool
from repro.serving.metrics import MetricsCollector
from repro.serving.spec_decode import DRAFTERS, Drafter, make_drafter, \
    make_spec_verify
from repro.serving.prefix_cache import PrefixCache, SessionStore, \
    block_hashes
from repro.serving.sampling import GREEDY, SamplingParams, sample_tokens
from repro.serving.scheduler import Request, SlotScheduler

__all__ = ["GenerationEngine", "Request", "make_serving_step",
           "make_fused_serving_step"]


class _BadLogits(RuntimeError):
    """A decode launch returned NaN/inf logits on a live lane (detected
    by the checked step, or reported by an injected ``'nan'`` fault)."""


class _ReplayNeeded(RuntimeError):
    """Both the normal launch and its degraded XLA retry failed: the
    engine must preempt the live lanes and replay them."""


def _jit(fn, name: str):
    """``jax.jit`` under a stable program name: a trace names each
    execution ``jit_<name>``, so the decode and fused programs, their
    sampled variants and their degraded XLA twins stay told apart."""
    fn.__name__ = fn.__qualname__ = name
    return jax.jit(fn)


class _LaunchSpans:
    """Host spans of the continuous loop, on the profiler's clock.

    One ``engine.iteration`` span per loop iteration, opened once the
    ``on_iteration`` hook has returned, and under it one span per phase
    of a launch, in this order: ``engine.admit`` (lifecycle pass,
    admission, block reservation, preemption), ``engine.inputs`` (host
    arrays and their copies to the device), ``engine.dispatch`` (the
    jitted call up to its return), ``engine.wait`` (the blocking reads
    of the launch's outputs) and ``engine.emit`` (per-lane bookkeeping,
    ``on_token``, finishing). Entering a phase ends the one before; the
    phase already open is not entered twice. Every phase carries the
    launch number; the iteration carries the number and the program of
    its launch (``decode``, ``fused``, ``chunk`` or ``verify``), as does
    ``engine.dispatch``, which for a fused launch also carries its token
    rows (``rows``, batch x chunk) and the rows that hold a token
    (``live``). With no profiler running a span costs under a
    microsecond.
    """

    def __init__(self):
        self._iter = self._phase = self._name = None
        self._launch = self._program = None

    def begin(self, launch: int) -> None:
        """Open an iteration whose next launch is number ``launch``."""
        self.end()
        self._iter = TraceAnnotation("engine.iteration")
        self._iter.__enter__()
        self._launch = launch
        self.phase("engine.admit")

    def phase(self, name: str, launch: Optional[int] = None,
              program: Optional[str] = None, **counts: int) -> None:
        if self._iter is None or (name == self._name and launch is None
                                  and program is None):
            return
        if self._phase is not None:
            self._phase.__exit__(None, None, None)
        if launch is not None:
            self._launch = launch
        meta = dict(counts, launch=self._launch)
        if program is not None:
            self._program = meta["program"] = program
        self._name = name
        self._phase = TraceAnnotation(name, **meta)
        self._phase.__enter__()

    def end(self) -> None:
        """Close the open phase and iteration, if any."""
        if self._phase is not None:
            self._phase.__exit__(None, None, None)
        if self._iter is not None:
            if self._program is not None:
                self._iter.set_metadata(launch=self._launch,
                                        program=self._program)
            self._iter.__exit__(None, None, None)
        self.__init__()


def make_serving_step(cfg, sample: bool = True, check: bool = False):
    """decode-one-token + select-next, as a single jit-able program.

    ``sample=True``: (params, cache, tokens (B,1), pos (B,), live (B,),
    temperature (B,), top_k (B,), top_p (B,), key) -> (next (B,), cache).
    ``sample=False`` is the greedy fast path — same contract minus the
    sampling arrays and key (argmax only, measurably cheaper per step on
    CPU than the full sampler; the engine uses it whenever no live lane
    has temperature > 0, which keeps greedy serving at wave step cost).

    ``check=True`` appends a third output ``bad`` (B,) bool: True where
    a *live* lane's logits contain NaN/inf — the health probe the
    fault-recovery path keys on (a corrupted v2 gap stream poisons
    logits silently; this converts that into a typed, retryable
    failure). The token outputs are computed identically, so checked
    and unchecked variants emit the same streams.

    Both variants take two trailing optional arrays: ``pages`` (B,
    max_blocks) mirrors the paged-KV page tables into the cache
    (kv_layout='paged'), ``reset`` (B,) zeroes recycled lanes' recurrent
    state (continuous ssm/hybrid serving). None (the default) keeps the
    contiguous-attention contract bit-for-bit.
    """
    decode = make_decode_step(cfg)

    def step(params, cache, tokens, pos, live, temperature, top_k, top_p,
             key, pages=None, reset=None):
        logits, cache = decode(params, cache, tokens, pos, pages=pages,
                               reset=reset)
        with jax.named_scope("sampling"):
            toks = sample_tokens(logits, key, temperature, top_k, top_p,
                                 live=live)
            if check:
                bad = live & ~jnp.isfinite(logits).all(axis=-1)
                return toks, cache, bad
            return toks, cache

    def greedy_step(params, cache, tokens, pos, live, pages=None,
                    reset=None):
        logits, cache = decode(params, cache, tokens, pos, pages=pages,
                               reset=reset)
        with jax.named_scope("sampling"):
            toks = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            if check:
                bad = live & ~jnp.isfinite(logits).all(axis=-1)
                return jnp.where(live, toks, 0), cache, bad
            return jnp.where(live, toks, 0), cache

    return step if sample else greedy_step


def make_fused_serving_step(cfg, sample: bool = True, check: bool = False):
    """Fused mixed prefill/decode iteration as a single jit-able program.

    Same contract family as ``make_serving_step``, but over an S-token
    chunk: (params, cache, tokens (B, S), pos (B,), seq_lens (B,), live
    (B,), [temperature, top_k, top_p, key,] pages) -> (next (B,), cache
    [, bad (B,)]). Each lane consumes its first ``seq_lens[i]`` chunk
    tokens (``> 1``: bulk prompt admission, ``== 1``: the decode token
    in column 0, ``== 0``: idle, fully write-masked) and the returned
    token is sampled from that lane's logits at its own last valid
    column — so one launch replaces the chunk-pass + decode-pass pair
    of a mixed continuous-batching iteration. The engine ignores the
    sampled token for lanes still inside their prompt (their logits are
    real but mid-prompt); ``sample=False`` / ``check=True`` mirror the
    decode program's greedy fast path and NaN health probe.
    """
    fused = make_fused_step(cfg)

    def step(params, cache, tokens, pos, lens, live, temperature, top_k,
             top_p, key, pages=None):
        logits, cache = fused(params, cache, tokens, pos, lens, pages=pages)
        with jax.named_scope("sampling"):
            toks = sample_tokens(logits, key, temperature, top_k, top_p,
                                 live=live)
            if check:
                bad = live & ~jnp.isfinite(logits).all(axis=-1)
                return toks, cache, bad
            return toks, cache

    def greedy_step(params, cache, tokens, pos, lens, live, pages=None):
        logits, cache = fused(params, cache, tokens, pos, lens, pages=pages)
        with jax.named_scope("sampling"):
            toks = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            if check:
                bad = live & ~jnp.isfinite(logits).all(axis=-1)
                return jnp.where(live, toks, 0), cache, bad
            return jnp.where(live, toks, 0), cache

    return step if sample else greedy_step


def default_fused_step() -> bool:
    """Engine default for ``fused_step`` (ICQ_FUSED_STEP, default on):
    whether a chunked-prefill continuous engine folds the decode token
    into the chunk program and runs mixed prefill+decode iterations as
    ONE launch. Only consulted when chunked prefill is active
    (``prefill_chunk > 1`` on the continuous engine); off = the split
    two-launch chunk + decode structure."""
    env = os.environ.get("ICQ_FUSED_STEP")
    if not env:  # unset or set-but-empty
        return True
    low = env.lower()
    if low in ("1", "true", "yes", "on"):
        return True
    if low in ("0", "false", "no", "off"):
        return False
    raise ValueError(f"ICQ_FUSED_STEP must be a boolean flag, got {env!r}")


def default_prefill_chunk() -> int:
    """Engine default for ``prefill_chunk`` (ICQ_PREFILL_CHUNK, default 1 =
    walk prompts token-by-token inside the decode program, the pre-chunking
    behavior)."""
    env = os.environ.get("ICQ_PREFILL_CHUNK")
    if not env:  # unset or set-but-empty
        return 1
    try:
        chunk = int(env)
    except ValueError:
        raise ValueError(
            f"ICQ_PREFILL_CHUNK must be an integer, got {env!r}")
    if chunk < 1:
        raise ValueError(
            f"ICQ_PREFILL_CHUNK must be >= 1, got {chunk}")
    return chunk


def default_kv_layout() -> str:
    """Engine default for ``kv_layout`` (ICQ_KV_LAYOUT, default
    'contiguous' — the pre-paging slot cache, bit-for-bit)."""
    env = os.environ.get("ICQ_KV_LAYOUT")
    if not env:
        return "contiguous"
    if env not in ("contiguous", "paged"):
        raise ValueError(
            f"ICQ_KV_LAYOUT must be 'contiguous' or 'paged', got {env!r}")
    return env


def default_kv_block_size():
    """Paged-KV block size default (ICQ_KV_BLOCK_SIZE, default 16 rows).
    ``'auto'`` consults the shared JSON autotune cache for a block size
    recorded by ``kernels.autotune.autotune_kv_block_size`` (the
    fragmentation-vs-table-overhead sweep), falling back to 16 on a
    cache miss — the engine resolves it against its ``max_len``."""
    env = os.environ.get("ICQ_KV_BLOCK_SIZE")
    if not env:
        return 16
    if env == "auto":
        return "auto"
    try:
        bs = int(env)
    except ValueError:
        raise ValueError(
            f"ICQ_KV_BLOCK_SIZE must be an integer or 'auto', got {env!r}")
    if bs < 1:
        raise ValueError(f"ICQ_KV_BLOCK_SIZE must be >= 1, got {bs}")
    return bs


def default_prefix_cache() -> bool:
    """Engine default for ``prefix_cache`` (ICQ_PREFIX_CACHE, default
    off — the PR-7 engine bit-for-bit). On, the paged continuous engine
    shares identical prompt prefixes copy-on-write and retains session
    chains (serving/prefix_cache.py); requires ``kv_layout='paged'``."""
    env = os.environ.get("ICQ_PREFIX_CACHE")
    if not env:  # unset or set-but-empty
        return False
    low = env.lower()
    if low in ("1", "true", "yes", "on"):
        return True
    if low in ("0", "false", "no", "off"):
        return False
    raise ValueError(f"ICQ_PREFIX_CACHE must be a boolean flag, got {env!r}")


def default_session_ttl() -> float:
    """Session idle TTL default in seconds on the engine clock
    (ICQ_SESSION_TTL, default 300): a session whose last turn finished
    longer ago than this is dropped by the lifecycle pass and its
    retained blocks unpinned. 0 expires sessions at the next sweep —
    the deterministic testing hook, mirroring ``max_queue_wait_s=0``."""
    env = os.environ.get("ICQ_SESSION_TTL")
    if not env:
        return 300.0
    try:
        ttl = float(env)
    except ValueError:
        raise ValueError(f"ICQ_SESSION_TTL must be a number, got {env!r}")
    if ttl < 0:
        raise ValueError(f"ICQ_SESSION_TTL must be >= 0, got {ttl}")
    return ttl


def default_max_queue() -> Optional[int]:
    """Bounded-submit-queue default (ICQ_MAX_QUEUE; unset = None =
    unbounded, the pre-backpressure behavior)."""
    env = os.environ.get("ICQ_MAX_QUEUE")
    if not env:
        return None
    try:
        mq = int(env)
    except ValueError:
        raise ValueError(f"ICQ_MAX_QUEUE must be an integer, got {env!r}")
    if mq < 0:
        raise ValueError(f"ICQ_MAX_QUEUE must be >= 0, got {mq}")
    return mq


def default_shed_policy() -> str:
    """Backpressure shed policy default (ICQ_SHED_POLICY, default
    'reject' — turn away the *new* request; 'shed-oldest' drops the
    longest-queued request instead)."""
    env = os.environ.get("ICQ_SHED_POLICY")
    if not env:
        return "reject"
    if env not in ("reject", "shed-oldest"):
        raise ValueError(
            f"ICQ_SHED_POLICY must be 'reject' or 'shed-oldest', got {env!r}")
    return env


def default_degrade_steps() -> int:
    """Degraded-mode stickiness default (ICQ_DEGRADE_STEPS, default 8):
    clean launches on the XLA fallback arm before dispatch returns to
    the kernel arms after a recovered fault."""
    env = os.environ.get("ICQ_DEGRADE_STEPS")
    if not env:
        return 8
    try:
        n = int(env)
    except ValueError:
        raise ValueError(
            f"ICQ_DEGRADE_STEPS must be an integer, got {env!r}")
    if n < 1:
        raise ValueError(f"ICQ_DEGRADE_STEPS must be >= 1, got {n}")
    return n


def default_spec_decode() -> bool:
    """Engine default for ``spec_decode`` (ICQ_SPEC_DECODE, default off
    — the pre-speculation engine bit-for-bit). On, the continuous engine
    runs draft-and-verify iterations whenever every live lane is
    greedily decoding (serving/spec_decode.py); greedy output is
    token-identical either way, only the launch count changes."""
    env = os.environ.get("ICQ_SPEC_DECODE")
    if not env:  # unset or set-but-empty
        return False
    low = env.lower()
    if low in ("1", "true", "yes", "on"):
        return True
    if low in ("0", "false", "no", "off"):
        return False
    raise ValueError(f"ICQ_SPEC_DECODE must be a boolean flag, got {env!r}")


def default_spec_k() -> int:
    """Draft length default (ICQ_SPEC_K, default 4): tokens proposed per
    lane per speculative iteration; the verify launch scores k+1
    positions per lane."""
    env = os.environ.get("ICQ_SPEC_K")
    if not env:
        return 4
    try:
        k = int(env)
    except ValueError:
        raise ValueError(f"ICQ_SPEC_K must be an integer, got {env!r}")
    if k < 1:
        raise ValueError(f"ICQ_SPEC_K must be >= 1, got {k}")
    return k


def default_spec_draft() -> str:
    """Drafter default (ICQ_SPEC_DRAFT, default 'ngram' — host-side
    prompt-lookup, zero extra launches). See serving/spec_decode.py for
    the registry: ngram | self2bit | tiny | reject."""
    env = os.environ.get("ICQ_SPEC_DRAFT")
    if not env:
        return "ngram"
    if env not in DRAFTERS:
        raise ValueError(
            f"ICQ_SPEC_DRAFT must be one of {'|'.join(DRAFTERS)}, "
            f"got {env!r}")
    return env


def _continuous_supported(cfg, max_len: int) -> Optional[str]:
    """None if the config can run the continuous engine, else the reason."""
    if cfg.is_encdec:
        return "enc-dec models admit encoder output wave-at-a-time"
    if cfg.sliding_window and cfg.sliding_window < max_len:
        return "sliding-window ring cache has a batch-global position column"
    return None


class GenerationEngine:
    def __init__(self, params, cfg, batch_size: int, max_len: int,
                 weight_cache: str = "prepared",
                 runtime_fmt: Optional[str] = None,
                 mode: str = "auto",
                 sampling: Optional[SamplingParams] = None,
                 seed: int = 0,
                 prefill_chunk: Optional[int] = None,
                 kv_layout: Optional[str] = None,
                 kv_block_size: Optional[int] = None,
                 kv_blocks: Optional[int] = None,
                 clock: Optional[Callable[[], float]] = None,
                 max_queue: Optional[int] = None,
                 shed_policy: Optional[str] = None,
                 faults: Optional[FaultInjector] = None,
                 degrade_steps: Optional[int] = None,
                 fused_step: Optional[bool] = None,
                 prefix_cache: Optional[bool] = None,
                 session_ttl: Optional[float] = None,
                 spec_decode: Optional[bool] = None,
                 spec_k: Optional[int] = None,
                 spec_draft=None,
                 draft_params=None):
        kw = {"fmt": runtime_fmt} if runtime_fmt is not None else {}
        raw_params = params   # the self2bit drafter re-quantizes these
        self.params = prepare_serving_params(params, mode=weight_cache, **kw)
        self.cfg = cfg
        self.batch_size = batch_size
        self.max_len = max_len
        self.sampling = sampling if sampling is not None else GREEDY
        if prefill_chunk is None:
            prefill_chunk = default_prefill_chunk()
        if prefill_chunk < 1:
            raise ValueError(
                f"prefill_chunk must be >= 1, got {prefill_chunk}")
        self.prefill_chunk = int(prefill_chunk)
        if self.prefill_chunk > 1 and cfg.family in ("ssm", "hybrid"):
            import warnings

            warnings.warn(
                f"chunked prefill is not supported for the {cfg.family!r} "
                f"mixer (no per-position validity masking for recurrent "
                f"state); falling back to prefill_chunk=1", stacklevel=2)
            self.prefill_chunk = 1

        why_not = _continuous_supported(cfg, max_len)
        if mode == "auto":
            mode = "wave" if why_not else "continuous"
        elif mode == "continuous" and why_not:
            raise NotImplementedError(
                f"mode='continuous' unsupported for this config: {why_not}; "
                f"use mode='wave'")
        elif mode not in ("continuous", "wave"):
            raise ValueError(f"mode must be 'auto'|'continuous'|'wave', "
                             f"got {mode!r}")
        self.mode = mode
        if self.mode == "wave" and self.sampling != GREEDY:
            import warnings

            warnings.warn(
                "the wave engine is greedy-only: the engine-level "
                "sampling parameters are ignored in mode='wave'",
                stacklevel=2)

        # ---- KV-cache layout (contiguous slot rows vs paged block pool)
        if kv_layout is None:
            kv_layout = default_kv_layout()
        if kv_layout not in ("contiguous", "paged"):
            raise ValueError(f"kv_layout must be 'contiguous' or 'paged', "
                             f"got {kv_layout!r}")
        if kv_layout == "paged":
            if self.mode != "continuous":
                raise NotImplementedError(
                    "kv_layout='paged' requires the continuous engine "
                    "(the wave engine rebuilds a contiguous cache per wave)")
            if cfg.family == "ssm":
                raise NotImplementedError(
                    "kv_layout='paged' needs an attention KV cache; the "
                    "'ssm' mixer carries recurrent state only")
        self.kv_layout = kv_layout
        if kv_block_size is None:
            kv_block_size = default_kv_block_size()
        if kv_block_size == "auto":
            # block-size sweep winner for this cache cap (the shared
            # JSON autotune cache); static default on a miss
            from repro.kernels import autotune

            kv_block_size = autotune.kv_block_size_for(max_len) or 16
        self.kv_block_size = int(kv_block_size)
        if self.kv_block_size < 1:
            raise ValueError(
                f"kv_block_size must be >= 1, got {self.kv_block_size}")
        # page-table width: a lane never maps more than the cache cap
        self._n_pt = -(-max_len // self.kv_block_size)
        if kv_blocks is None:
            # default pool = contiguous capacity (batch * max_len rows):
            # same worst-case footprint, but blocks only charge HBM-rows
            # that are actually mapped to a lane. Shrink to oversubscribe.
            kv_blocks = batch_size * self._n_pt
        self.kv_blocks = int(kv_blocks)
        if self.kv_layout == "paged" and self.kv_blocks < 1:
            raise ValueError(f"kv_blocks must be >= 1, got {self.kv_blocks}")

        # ---- prefix cache + sessions (serving/prefix_cache.py)
        if prefix_cache is None:
            prefix_cache = default_prefix_cache()
        self.prefix_cache = bool(prefix_cache)
        if self.prefix_cache:
            if self.kv_layout != "paged":
                raise ValueError(
                    "prefix_cache=True requires kv_layout='paged' (prefix "
                    "sharing maps physical blocks through page tables)")
            if cfg.family in ("ssm", "hybrid"):
                raise NotImplementedError(
                    f"prefix_cache is not supported for the {cfg.family!r} "
                    f"mixer: recurrent state has no per-position rows to "
                    f"share, so a warm start past position 0 cannot be "
                    f"reconstructed from cached blocks")
        self.session_ttl = (default_session_ttl() if session_ttl is None
                            else float(session_ttl))
        if self.session_ttl < 0:
            raise ValueError(
                f"session_ttl must be >= 0, got {self.session_ttl}")
        self._prefix = PrefixCache() if self.prefix_cache else None
        self._sessions = SessionStore() if self.prefix_cache else None
        self._session_rid: Dict[str, int] = {}   # in-flight request per sid
        self._pending_match: Dict[int, tuple] = {}  # rid -> pinned match
        self._cache = None   # persistent device cache (prefix-cache runs)
        # COW tail fork as ONE jitted program (src/dst are traced scalars,
        # so every fork reuses the same trace); built lazily on first use
        self._fork_block = None

        self._decode = _jit(make_decode_step(cfg), "wave_decode_step")
        # continuous path: checked variants (tokens identical to the
        # unchecked programs; the extra `bad` output is the NaN probe the
        # recovery path keys on)
        self._step = _jit(make_serving_step(cfg, check=True),
                          "decode_step_sampled")
        self._step_greedy = _jit(
            make_serving_step(cfg, sample=False, check=True), "decode_step")
        # degraded twins: the *same* programs, but their (lazy) first
        # trace happens under forced_backend('xla') — every matmul is
        # pinned to the bitwise-exact pure-XLA arm. Distinct jit objects
        # so the two arms never share a compilation cache entry.
        self._step_xla = _jit(make_serving_step(cfg, check=True),
                              "decode_step_sampled_xla")
        self._step_greedy_xla = _jit(
            make_serving_step(cfg, sample=False, check=True),
            "decode_step_xla")
        # recurrent mixers need the lane-reset mask on every decode launch
        self._needs_reset = cfg.family in ("ssm", "hybrid")
        # chunked prefill's device programs. With fused_step (the default
        # whenever chunking is active) the chunk and decode programs of a
        # mixed iteration collapse into ONE fused program — the decode
        # token rides the chunk's token axis and sampling happens in the
        # same launch; the split chunk program is then never built. With
        # fused_step=False (ICQ_FUSED_STEP=0) the PR-4 two-launch
        # structure is kept bit-for-bit. chunk=1 keeps the PR-3
        # single-program engine: neither program is built.
        chunking = self.prefill_chunk > 1 and self.mode == "continuous"
        if fused_step is None:
            fused_step = default_fused_step()
        self.fused_step = bool(fused_step) and chunking
        self._fused = self._fused_greedy = None
        self._fused_xla = self._fused_greedy_xla = None
        if self.fused_step:
            self._fused = _jit(make_fused_serving_step(cfg, check=True),
                               "fused_step_sampled")
            self._fused_greedy = _jit(
                make_fused_serving_step(cfg, sample=False, check=True),
                "fused_step")
            # degraded twins (same pattern as the decode programs above)
            self._fused_xla = _jit(
                make_fused_serving_step(cfg, check=True),
                "fused_step_sampled_xla")
            self._fused_greedy_xla = _jit(
                make_fused_serving_step(cfg, sample=False, check=True),
                "fused_step_xla")
        # second persistent jitted program: S-token prompt-chunk admission
        self._chunk_step = (
            jax.jit(make_prefill_chunk_step(cfg))
            if chunking and not self.fused_step else None)
        self._chunk_step_xla = (
            _jit(make_prefill_chunk_step(cfg), "prefill_chunk_step_xla")
            if self._chunk_step is not None else None)
        if chunking:
            from repro.kernels import autotune

            # chunk matmuls carry M = batch * chunk tokens: give the
            # autotuner (and backend.arm_blocks at call time) a bucket at
            # that M so the large-M arm can block for the chunk shape.
            autotune.register_prefill_m(batch_size * self.prefill_chunk)
        self._sched = SlotScheduler(batch_size)
        self._pool: Optional[KVBlockPool] = None    # built per run (paged)
        self._pages_dev = None    # device mirror of the pool's page table
        self._pages_ver = -1
        self._folded: Dict[int, int] = {}   # rid -> generated tokens already
        #                                     folded into the prompt (preempt)
        self._key = jax.random.PRNGKey(seed)
        self._clock = clock
        self._real_clock = clock is None
        self._t0: Optional[float] = None
        self._skew = 0.0
        self.completed: Dict[int, Request] = {}
        self.metrics = MetricsCollector()

        # ---- fault tolerance (see module doc)
        self.max_queue = default_max_queue() if max_queue is None \
            else int(max_queue)
        if self.max_queue is not None and self.max_queue < 0:
            raise ValueError(f"max_queue must be >= 0, got {self.max_queue}")
        self.shed_policy = (default_shed_policy() if shed_policy is None
                            else shed_policy)
        if self.shed_policy not in ("reject", "shed-oldest"):
            raise ValueError(f"shed_policy must be 'reject' or "
                             f"'shed-oldest', got {self.shed_policy!r}")
        # faults=None reads the ICQ_FAULT_* env knobs (normally unset ->
        # no injector at all; pass an explicit FaultInjector to drive a
        # storm programmatically)
        self.faults = FaultInjector.from_env() if faults is None else faults
        self.degrade_steps = (default_degrade_steps() if degrade_steps is None
                              else int(degrade_steps))
        if self.degrade_steps < 1:
            raise ValueError(
                f"degrade_steps must be >= 1, got {self.degrade_steps}")
        # ---- speculative decoding (serving/spec_decode.py)
        if spec_decode is None:
            spec_decode = default_spec_decode()
        self.spec_decode = bool(spec_decode)
        self.spec_k = default_spec_k() if spec_k is None else int(spec_k)
        if self.spec_k < 1:
            raise ValueError(f"spec_k must be >= 1, got {self.spec_k}")
        if spec_draft is None:
            spec_draft = default_spec_draft()
        self.spec_draft = (spec_draft.name if isinstance(spec_draft, Drafter)
                           else spec_draft)
        self._drafter: Optional[Drafter] = None
        self._verify = None
        if self.spec_decode:
            if self.mode != "continuous":
                raise NotImplementedError(
                    "spec_decode=True requires the continuous engine "
                    "(the wave engine has no per-lane positions to rewind)")
            if cfg.family in ("ssm", "hybrid"):
                raise NotImplementedError(
                    f"spec_decode needs positional KV rollback; the "
                    f"{cfg.family!r} mixer carries recurrent state that "
                    f"cannot rewind past a rejected draft")
            if isinstance(spec_draft, Drafter):
                self._drafter = spec_draft   # injected (tests, custom)
            else:
                self._drafter = make_drafter(
                    spec_draft, raw_params, cfg, batch_size, max_len,
                    weight_cache=weight_cache, prepare_kw=kw,
                    draft_params=draft_params, seed=seed)
            self._verify = _jit(make_spec_verify(cfg), "verify_step")
            from repro.kernels import autotune

            # the verify launch carries M = batch * (spec_k + 1) tokens:
            # give the autotuner a bucket at that M so the large-M
            # dequant+MXU arm can block for the verify shape
            autotune.register_prefill_m(batch_size * (self.spec_k + 1))
        self._draft_mark = 0     # drafter.launches already ledgered

        self._launch_no = 0           # global launch counter (decode+chunk)
        self._spans = _LaunchSpans()  # host spans of the continuous loop
        self._degraded_left = 0       # sticky degraded-mode countdown
        self._cancel_pending: set = set()   # rids awaiting cancellation
        self._replayed: Dict[int, int] = {}  # rid -> replay count (cap 2)
        self._replay_cap = 2

        # ---- service-layer hooks (serving/replica.py) — both inert by
        # default, so an engine used directly is bit-for-bit the PR-8
        # engine. ``on_iteration`` is called at the top of every
        # continuous-mode iteration (before the lifecycle pass): the
        # replica supervisor uses it to drain its inbox mid-run, beat
        # its heartbeat and raise to simulate a hard crash. A hook that
        # submits or cancels takes effect the same iteration.
        self.on_iteration: Optional[Callable[[], None]] = None
        self._draining = False

    # ------------------------------------------------------------------
    def submit(self, req: Request, session: Optional[str] = None) -> bool:
        """Enqueue a request; returns False when backpressure shed it.

        Invalid requests (empty prompt, prompt that cannot fit,
        duplicate rid, paged-unservable) still raise — those are caller
        bugs, not load. A shed request terminates immediately with
        status ``'rejected'`` and appears in ``run()``'s results like
        every other submission, so callers never lose track of a rid.

        ``session`` (or ``req.session``) names a multi-turn session on a
        prefix-cache engine: the finished turn's KV blocks stay pinned
        under that id (TTL/LRU-bounded) and the next turn's prompt
        warm-starts past the longest shared prefix — only the delta is
        prefilled. One request per session may be in flight at a time.
        """
        if session is not None:
            req.session = session
        if req.session is not None:
            if self._sessions is None:
                raise ValueError(
                    f"request {req.rid}: session={req.session!r} requires "
                    f"an engine built with prefix_cache=True "
                    f"(kv_layout='paged')")
            other = self._session_rid.get(req.session)
            if other is not None:
                raise ValueError(
                    f"request {req.rid}: session {req.session!r} already "
                    f"has request {other} in flight (one turn at a time)")
        n = len(req.prompt)
        if n == 0:
            raise ValueError(f"request {req.rid}: empty prompt")
        if n >= self.max_len:
            raise ValueError(
                f"request {req.rid}: prompt length {n} does not fit "
                f"max_len={self.max_len} (needs at most max_len - 1 prompt "
                f"positions to generate anything); raise max_len or "
                f"truncate the prompt")
        if req.rid in self.metrics.requests:
            raise ValueError(f"duplicate request id {req.rid}")
        if self.kv_layout == "paged":
            # a request must be servable by the pool *alone* (this is
            # also what guarantees preemption always makes progress: a
            # lane with the whole pool to itself can always finish)
            need = -(-min(n + req.max_new_tokens, self.max_len)
                     // self.kv_block_size)
            if need > self.kv_blocks:
                raise ValueError(
                    f"request {req.rid}: needs {need} KV blocks "
                    f"(prompt {n} + budget {req.max_new_tokens} tokens at "
                    f"block_size={self.kv_block_size}) but the pool only "
                    f"has {self.kv_blocks}; raise kv_blocks or shrink the "
                    f"request")
        if (self.mode == "wave" and req.sampling is not None
                and req.sampling != GREEDY):
            import warnings

            warnings.warn(
                f"request {req.rid}: per-request sampling parameters are "
                f"ignored by the greedy-only wave engine", stacklevel=2)
        if self._draining:
            # drain refuses new admissions exactly like a backpressure
            # shed: the request still terminates (status 'rejected'),
            # preserving the exactly-once typed-status guarantee while
            # in-flight and already-queued work runs to completion
            self.metrics.on_submit(req.rid, req.arrival_time, n)
            self._terminal_queued(req, req.arrival_time, "rejected")
            return False
        if (self.max_queue is not None
                and self._sched.queue_depth >= self.max_queue):
            if self.shed_policy == "reject":
                # the new request is the victim: record it (metrics +
                # results) and turn it away
                self.metrics.on_submit(req.rid, req.arrival_time, n)
                self._terminal_queued(req, req.arrival_time, "rejected")
                return False
            victim = self._sched.shed_oldest()   # 'shed-oldest'
            if victim is not None:
                self._terminal_queued(victim, req.arrival_time, "rejected")
        self.metrics.on_submit(req.rid, req.arrival_time, n)
        self._sched.submit(req)
        if req.session is not None:
            self._session_rid[req.session] = req.rid
        return True

    def cancel(self, rid: int) -> bool:
        """Request cancellation of ``rid``; safe from ``on_token``.

        Returns True when the cancellation is pending (it takes effect
        at the next iteration boundary: a queued request leaves the
        queue, a running lane frees its slot and paged blocks — both
        with status ``'cancelled'`` and partial output kept), False when
        the request already finished. Unknown rids raise KeyError.
        """
        if rid not in self.metrics.requests:
            raise KeyError(f"unknown request id {rid}")
        if rid in self.completed:
            return False
        self._cancel_pending.add(rid)
        return True

    def request_drain(self) -> None:
        """Refuse new submissions from now on (graceful drain).

        Already-queued and running requests finish normally; subsequent
        ``submit()`` calls terminate immediately with status
        ``'rejected'``. Sticky for the engine's lifetime — a drained
        replica is replaced by a fresh engine, never re-opened.
        """
        self._draining = True

    @property
    def draining(self) -> bool:
        return self._draining

    def has_work(self) -> bool:
        """True while any request is queued or running (scheduler view)."""
        return self._sched.has_work()

    def _now(self) -> float:
        raw = time.monotonic() if self._real_clock else self._clock()
        if self._t0 is None:
            self._t0 = raw
        return raw - self._t0 + self._skew

    def _idle_until(self, t: float) -> None:
        """Nothing admissible: wait out the gap to the next arrival."""
        now = self._now()
        if t <= now:
            return
        if self._real_clock:
            time.sleep(t - now)
        else:
            self._skew += t - now  # virtual clock: fast-forward

    # ------------------------------------------------------------------
    # continuous mode
    # ------------------------------------------------------------------

    def _finish(self, slot: int, t: float, live: np.ndarray,
                pos: np.ndarray, tokens: np.ndarray,
                status: str = "ok") -> None:
        req = self._sched.release(slot)
        if self._pool is not None:
            if status == "ok" and self.prefix_cache:
                # index the finished chain / retain the session chain
                # BEFORE the lane's references drop, so shared blocks
                # never transit refcount 0 on their way into the cache
                self._retain_prefix(slot, req, int(pos[slot]), t)
            self._pool.release(slot)   # lane references dropped same step
        if req.session is not None:
            self._session_rid.pop(req.session, None)
        self._folded.pop(req.rid, None)
        self._replayed.pop(req.rid, None)
        self._cancel_pending.discard(req.rid)
        req.status = status
        self.metrics.on_finish(req.rid, t, len(req.generated), status=status)
        self.completed[req.rid] = req
        live[slot] = False
        pos[slot] = 0
        tokens[slot, 0] = 0

    def _terminal_queued(self, req: Request, t: float, status: str) -> None:
        """Terminal path for a request that never occupies a slot again
        (queued expiry/cancellation, backpressure shed). Partial output
        from a pre-preemption life is kept on the request."""
        if req.session is not None:
            self._session_rid.pop(req.session, None)
        self._folded.pop(req.rid, None)
        self._replayed.pop(req.rid, None)
        self._cancel_pending.discard(req.rid)
        req.status = status
        self.metrics.on_finish(req.rid, t, len(req.generated), status=status)
        self.completed[req.rid] = req

    def _lifecycle_pass(self, now: float, live: np.ndarray, pos: np.ndarray,
                        tokens: np.ndarray) -> bool:
        """Once-per-iteration deadline/cancellation enforcement.

        Queued requests past ``max_queue_wait_s`` or ``deadline_s`` (or
        cancelled) leave the queue without ever occupying a slot; live
        lanes past ``deadline_s`` finish as ``'timeout'`` with whatever
        they generated, cancelled lanes as ``'cancelled'``. Returns True
        when anything changed (the caller must refresh the device ctrl
        mirror). Comparisons are ``>=`` so a zero deadline/wait expires
        deterministically under the virtual clock (which only advances
        across idle gaps) — ``max_queue_wait_s=0`` is the deterministic
        'never admitted' testing hook.
        """
        sched = self._sched
        changed = False

        def queued_verdict(req: Request) -> Optional[str]:
            if req.rid in self._cancel_pending:
                return "cancelled"
            waited = now - req.arrival_time
            if req.deadline_s is not None and waited >= req.deadline_s:
                return "expired"
            if (req.max_queue_wait_s is not None
                    and waited >= req.max_queue_wait_s):
                return "expired"
            return None

        for req in sched.drop_queued(lambda r: queued_verdict(r) is not None):
            self._terminal_queued(req, now, queued_verdict(req))
            changed = True
        for i in range(self.batch_size):
            if not live[i]:
                continue
            req = sched.slot(i).request
            if req.rid in self._cancel_pending:
                self._finish(i, now, live, pos, tokens, status="cancelled")
                changed = True
            elif (req.deadline_s is not None
                  and now - req.arrival_time >= req.deadline_s):
                self._finish(i, now, live, pos, tokens, status="timeout")
                changed = True
        if self._sessions is not None and len(self._sessions):
            # TTL sweep: idle sessions past ICQ_SESSION_TTL drop their
            # retained chains (in-flight sessions are exempt — their
            # next retention refreshes the stamp anyway)
            expired = self._sessions.expire(
                now, self.session_ttl, self._pool,
                protect=self._session_rid.keys())
            if expired:
                self.metrics.on_session_expired(len(expired))
        return changed

    # -- paged-KV admission / preemption -------------------------------

    def _admit_tokens(self, req: Request) -> int:
        """Positions an admission must be able to back: the whole prompt
        plus a minimum decode budget (one block's worth of generated
        tokens, or the remaining budget if smaller — a preempted request
        already folded its generated tokens into the prompt, so only the
        *unspent* budget counts), capped at the cache cap."""
        remaining = max(0, req.max_new_tokens - len(req.generated))
        return min(len(req.prompt) + min(remaining, self.kv_block_size),
                   self.max_len)

    def _admit_gate(self, req: Request) -> bool:
        pool = self._pool
        if not self.prefix_cache:
            return pool.free_blocks >= pool.blocks_for(
                self._admit_tokens(req))
        # prefix-aware gate: only the blocks NOT covered by the matched
        # prefix must come from the free list. The match is pinned
        # (temporary increfs) before any eviction runs, so LRU pressure
        # can never free the very blocks this admission is about to
        # share — and the pinned ids stay valid even if their cache
        # entries are evicted between gate and attach.
        m, shared, fork_src, via_session = self._match_for(req)
        need = pool.blocks_for(self._admit_tokens(req)) - len(shared)
        if pool.free_blocks < need and not self._evict_for(need):
            for b in shared:
                pool.decref(b)
            if fork_src is not None:
                pool.decref(fork_src)
            return False
        self._pending_match[req.rid] = (m, shared, fork_src, via_session)
        return True

    def _match_for(self, req: Request):
        """Longest warm prefix available for ``req``: the session chain
        (exact tokens, can warm-start mid-block) vs the hash cache
        (full blocks only), whichever matches more. Matched blocks are
        pinned with temporary increfs; the caller owns dropping them
        (after ``share`` re-references them lane-side, or on gate
        failure). Returns (m, shared_full_blocks, fork_src, via_session)
        where ``fork_src`` is the partially-matched block to COW-fork
        (None on a block-aligned match)."""
        pool = self._pool
        bs = pool.block_size
        L = len(req.prompt)
        now = self._now()
        m, chain, via_session = 0, [], False
        if req.session is not None:
            m, chain = self._sessions.match(req.session, req.prompt, now)
            m = min(m, L - 1)   # the decode step must consume >= 1 token
            via_session = m > 0
        hits = self._prefix.match(
            block_hashes(req.prompt, bs, n_blocks=(L - 1) // bs), now)
        if len(hits) * bs > m:
            m, chain, via_session = len(hits) * bs, hits, False
        nfull = m // bs
        shared = chain[:nfull]
        fork_src = chain[nfull] if m % bs else None
        for b in shared:
            pool.incref(b)
        if fork_src is not None:
            pool.incref(fork_src)
        return m, shared, fork_src, via_session

    def _evict_for(self, min_free: int) -> bool:
        """Pool-pressure gate for the caches: evict hash-cache entries
        (LRU leaves first), then idle sessions (LRU first), until the
        free list covers ``min_free`` blocks. True iff the target is met.

        Only sessions whose turn currently occupies a *slot* are
        protected. Protecting every submitted session would deadlock:
        with more queued sessions than the pool can pin, admission could
        never free enough blocks for anyone. A merely-queued session
        losing its chain costs a cold prefill, nothing more — and a
        running session's chain is mostly lane-shared anyway, so
        evicting it would barely free blocks while its retain-at-finish
        is imminent."""
        pool = self._pool
        if pool.free_blocks >= min_free:
            return True
        if self._prefix is not None:
            n = self._prefix.evict_until(pool, min_free)
            if n:
                self.metrics.on_prefix_evictions(n)
        if pool.free_blocks < min_free and self._sessions is not None:
            running = {s.request.rid
                       for s in self._sched.occupied().values()}
            n = self._sessions.evict_until(
                pool, min_free,
                protect=(sid for sid, rid in self._session_rid.items()
                         if rid in running))
            if n:
                self.metrics.on_session_evicted(n)
        return pool.free_blocks >= min_free

    def _attach_prefix(self, slot: int, req: Request, cache,
                       pos: np.ndarray, tokens: np.ndarray):
        """Admission-time warm start: map the matched blocks into the
        lane's page table, COW-fork the partially-matched tail block (if
        any), and advance the lane's position past the matched prefix —
        the existing chunked-prefill / teacher-forcing path then walks
        only the delta. Returns the (possibly fork-copied) cache."""
        pool = self._pool
        m, shared, fork_src, via_session = self._pending_match.pop(req.rid)
        forked = False
        if fork_src is not None:
            pool.share(slot, [*shared, fork_src])
            dst = pool.fork(slot, len(shared))
            if dst is None:
                # pool dry (cannot happen after a passed gate, but stay
                # safe): degrade to the block-aligned prefix
                pool.pop_last(slot)
                m = len(shared) * pool.block_size
            else:
                if self._fork_block is None:
                    self._fork_block = jax.jit(fork_cache_block)
                cache = self._fork_block(cache, jnp.int32(fork_src),
                                         jnp.int32(dst))
                forked = True
        elif shared:
            pool.share(slot, shared)
        # drop the temporary match pins: the lane now holds its own refs
        for b in shared:
            pool.decref(b)
        if fork_src is not None:
            pool.decref(fork_src)
        if m > 0:
            pos[slot] = m
            tokens[slot, 0] = int(req.prompt[m])
            self._sched.slot(slot).pos = m
        self.metrics.on_prefix_attach(m, forked=forked,
                                      via_session=via_session)
        return cache

    def _retain_prefix(self, slot: int, req: Request, nrows: int,
                       t: float) -> None:
        """Finish-time retention: index the lane's full blocks in the
        hash cache and (for session requests) pin the exact consumed
        chain under the session id. ``nrows`` is the lane's final
        position = tokens consumed; the last generated token was emitted
        but never consumed, so it is not part of the chain."""
        pool = self._pool
        if nrows < 1:
            return
        # tokens the lane consumed this life: the (possibly replay-
        # folded) prompt, then the generated tokens fed back after it
        folded = self._folded.get(req.rid, 0)
        seq = np.concatenate([
            np.asarray(req.prompt, np.int32),
            np.asarray(req.generated[folded:], np.int32),
        ])[:nrows]
        chain = pool.lane_chain(slot)[: pool.blocks_for(len(seq))]
        hashes = block_hashes(seq, pool.block_size)
        created = self._prefix.insert(hashes, chain[: len(hashes)], pool, t)
        if created:
            self.metrics.on_prefix_insert(created)
        if req.session is not None:
            self._sessions.retain(req.session, seq, chain, pool, t)

    def _grow_evicting(self, lane: int, n_tokens: int) -> int:
        """``pool.grow`` that spends cached chains before letting a lane
        clip its chunk: under pool pressure, LRU cache entries and idle
        sessions give their pinned blocks back first."""
        pool = self._pool
        if self.prefix_cache:
            cap = pool.max_blocks_per_lane * pool.block_size
            need = (pool.blocks_for(min(n_tokens, cap))
                    - pool.lane_blocks(lane))
            if need > pool.free_blocks:
                self._evict_for(need)
        return pool.grow(lane, n_tokens)

    def _preempt(self, slot: int, t: float, live: np.ndarray,
                 pos: np.ndarray, tokens: np.ndarray) -> None:
        """Pool exhausted: evict a lane and requeue its request.

        Preempt-and-recompute, vLLM-style: generated tokens fold into
        the prompt, the request goes back to the *head* of the queue
        (keeps FIFO), and on re-admission the lane replays the extended
        prompt through teacher forcing. Greedy decoding makes the replay
        reproduce the identical continuation, so preemption never
        changes a greedy stream — only its timing.

        A **sampled** lane (temperature > 0) has no such guarantee: its
        replay draws fresh PRNG and silently diverges from the stream
        already handed to ``on_token``. Rather than return a stream no
        run can reproduce, the lane force-finishes with status
        ``'failed'`` (partial output kept) — the caller sees a typed
        loss, not quiet divergence.
        """
        st = self._sched.slot(slot)
        sp = (st.request.sampling if st.request.sampling is not None
              else self.sampling)
        if sp.temperature > 0.0:
            self._finish(slot, t, live, pos, tokens, status="failed")
            return
        req = self._sched.release(slot)
        if self._pool is not None:    # contiguous replay has no pool
            self._pool.release(slot)
        # fold only the not-yet-folded suffix: a request preempted a
        # second time must not duplicate tokens already in the prompt
        folded = self._folded.get(req.rid, 0)
        fresh = req.generated[folded:]
        if fresh:
            req.prompt = np.concatenate(
                [np.asarray(req.prompt, np.int32),
                 np.asarray(fresh, np.int32)])
            self._folded[req.rid] = len(req.generated)
        self._sched.requeue_front(req)
        self.metrics.on_preempt(req.rid, t)
        live[slot] = False
        pos[slot] = 0
        tokens[slot, 0] = 0

    def _ensure_decode_blocks(self, live: np.ndarray, pos: np.ndarray,
                              tokens: np.ndarray) -> None:
        """Back every live lane's next write position, preempting the
        youngest live lane when the pool runs dry. Oldest-first order
        gives long-running lanes (closest to finishing, holding the
        most blocks) priority; the victim is always the youngest live
        lane — the requester itself when it *is* the youngest (or the
        only lane), and then its requeued request later gets the pool
        to itself, which ``submit`` guaranteed is enough (progress is
        total).
        """
        pool, sched = self._pool, self._sched
        order = sorted((i for i in range(self.batch_size) if live[i]),
                       key=lambda i: sched.slot(i).seq)
        for i in order:
            while live[i] and not pool.ensure(i, int(pos[i]) + 1):
                # cached chains give their blocks back before any lane
                # is preempted: cache pressure must never cost running
                # work (the caches only hold HBM nobody else wanted)
                if self.prefix_cache and self._evict_for(1):
                    continue
                # youngest live lane overall — possibly the requester
                # itself (then the loop exits via live[i] going False and
                # the requeued request later gets the pool to itself)
                victim = max((j for j in range(self.batch_size) if live[j]),
                             key=lambda j: sched.slot(j).seq)
                self._preempt(victim, self._now(), live, pos, tokens)

    # -- fault recovery -------------------------------------------------

    def _decode_launch(self, cache, tokens, pos, ctrl, greedy_only, sub,
                       extra, live, fault):
        """One checked decode launch under the recovery policy.

        Returns (toks, cache), toks read back to the host. A launch that
        raises (injected or genuine RuntimeError) or whose logits come
        back non-finite on a live lane is retried **once** on the degraded
        XLA arm with identical inputs — including the PRNG subkey, so a
        recovered sampled launch draws the very tokens the failed one
        would have. Failure of the retry raises ``_ReplayNeeded``; the
        caller preempts and replays the live lanes. The failed launch's
        cache output is discarded (jitted steps are functional), so a
        retry never sees half-written state.
        """
        spans = self._spans
        spans.phase("engine.inputs")
        d_live, d_temp, d_topk, d_topp = ctrl
        t_dev, p_dev = jnp.asarray(tokens), jnp.asarray(pos)

        def run(degraded: bool):
            if greedy_only:
                prog = (self._step_greedy_xla if degraded
                        else self._step_greedy)
                args = (self.params, cache, t_dev, p_dev, d_live)
            else:
                prog = self._step_xla if degraded else self._step
                args = (self.params, cache, t_dev, p_dev, d_live,
                        d_temp, d_topk, d_topp, sub)
            ctx = (forced_backend("xla") if degraded
                   else contextlib.nullcontext())
            spans.phase("engine.dispatch", program="decode")
            with ctx:
                toks, cache2, bad = prog(*args, **extra)
            spans.phase("engine.wait")
            bad, toks = np.asarray(bad), np.asarray(toks)
            if bool((bad & live).any()):
                raise _BadLogits("non-finite logits on a live lane")
            return toks, cache2

        degraded = self._degraded_left > 0
        try:
            if fault == "raise":
                raise FaultInjected(
                    f"injected 'raise' at launch {self._launch_no - 1}")
            out = run(degraded)
            if fault == "nan":
                # the launch ran; its logits are reported poisoned
                raise _BadLogits(
                    f"injected 'nan' at launch {self._launch_no - 1}")
        except RuntimeError as e:   # FaultInjected / _BadLogits / XLA
            if fault is not None:
                self.metrics.on_fault(fault)
            else:
                self.metrics.on_fault(
                    "nan" if isinstance(e, _BadLogits) else "error")
            self._degraded_left = self.degrade_steps
            try:
                out = run(True)   # retry once, bitwise-exact XLA arm
            except RuntimeError:
                raise _ReplayNeeded("decode launch failed twice")
        spans.phase("engine.emit")
        if self._degraded_left > 0:
            self._degraded_left -= 1
            self.metrics.on_degraded_step()
        return out

    def _replay_live_lanes(self, t: float, live: np.ndarray,
                           pos: np.ndarray, tokens: np.ndarray) -> None:
        """Both launch attempts failed: preempt every live lane through
        the standing preempt-and-requeue machinery, so the whole batch
        replays from requeued prompts (greedy streams recompute
        identically; sampled lanes force-finish as 'failed' inside
        ``_preempt``). A request that needs more than ``_replay_cap``
        replays — a genuinely poisoned weight or model would otherwise
        loop forever — finishes as ``'failed'`` with partial output.
        """
        self.metrics.on_replay()
        for i in range(self.batch_size):
            if not live[i]:
                continue
            rid = self._sched.slot(i).request.rid
            n = self._replayed.get(rid, 0) + 1
            self._replayed[rid] = n
            if n > self._replay_cap:
                self._finish(i, t, live, pos, tokens, status="failed")
            else:
                self._preempt(i, t, live, pos, tokens)

    def _prefill_chunk_pass(self, cache, pos: np.ndarray, live: np.ndarray,
                            tokens: np.ndarray):
        """Drain bulk prompt through the chunk program, one launch.

        A lane's *bulk* is every prompt token except the last (the decode
        step must consume the last one so the first generated token's
        logits are unchanged). Returns (cache, True) after a launch, or
        (cache, False) when no live lane has bulk left — the caller then
        runs a decode step as usual. Lanes mid-decode (or ragged tails
        shorter than the chunk) ride along write-masked via seq_lens.
        """
        B = self.batch_size
        sched = self._sched
        S = self.prefill_chunk
        lens = np.zeros((B,), np.int32)
        for i in range(B):
            if live[i]:
                r = sched.slot(i).request
                lens[i] = min(S, max(0, len(r.prompt) - 1 - pos[i]))
                if lens[i] and self._pool is not None:
                    # paged: clip the chunk to what the pool can back
                    # right now (never preempt for prefill — a clipped
                    # lane just chunks less this launch, and the decode
                    # pass owns last-resort preemption)
                    backed = self._grow_evicting(
                        i, int(pos[i]) + int(lens[i]))
                    lens[i] = min(lens[i], max(0, backed - int(pos[i])))
        if not lens.any():
            return cache, False
        self._spans.phase("engine.inputs")
        ctoks = np.zeros((B, S), np.int32)
        for i in range(B):
            if lens[i]:
                r = sched.slot(i).request
                ctoks[i, : lens[i]] = r.prompt[pos[i]: pos[i] + lens[i]]
        # .copy(): argument transfers are async and pos mutates below —
        # the chunk step has no host-side output read to fence on.
        args = (self.params, cache, jnp.asarray(ctoks),
                jnp.asarray(pos.copy()), jnp.asarray(lens))
        fault = (self.faults.draw(self._launch_no)
                 if self.faults is not None else None)
        self._launch_no += 1

        def run(degraded: bool):
            prog = self._chunk_step_xla if degraded else self._chunk_step
            ctx = (forced_backend("xla") if degraded
                   else contextlib.nullcontext())
            pages = self._pages_mirror()
            self._spans.phase("engine.dispatch", program="chunk")
            with ctx:
                return prog(*args, pages=pages)

        degraded = self._degraded_left > 0
        try:
            if fault is not None:
                # the chunk step returns no logits and never touches the
                # allocator, so 'nan'/'alloc' draws degrade to 'raise'
                raise FaultInjected(
                    f"injected {fault!r} at chunk launch {self._launch_no - 1}")
            cache = run(degraded)
        except (FaultInjected, RuntimeError):
            self.metrics.on_fault(fault if fault is not None else "error")
            self._degraded_left = self.degrade_steps
            try:
                cache = run(True)   # retry once, bitwise-exact XLA arm
            except (FaultInjected, RuntimeError):
                raise _ReplayNeeded("chunk launch failed twice")
        self._spans.phase("engine.emit")
        if self._degraded_left > 0:
            self._degraded_left -= 1
            self.metrics.on_degraded_step()
        t_now = self._now()
        self.metrics.on_step(
            int(live.sum()), sched.queue_depth, t_now, kind="prefill",
            blocks_in_use=(None if self._pool is None
                           else self._pool.used_blocks),
            shared_blocks=(self._pool.shared_blocks()
                           if self.prefix_cache else None))
        self.metrics.on_prompt_tokens(int(lens.sum()), kind="prefill")
        for i in range(B):
            if lens[i]:
                pos[i] += int(lens[i])
                st = sched.slot(i)
                st.pos = int(pos[i])
                # next token to feed (the decode step consumes it when
                # every lane's bulk is drained)
                tokens[i, 0] = int(st.request.prompt[pos[i]])
        return cache, True

    def _fused_lens(self, live: np.ndarray, pos: np.ndarray) -> np.ndarray:
        """Per-lane token counts for one fused iteration: ``min(S,
        prompt remaining)`` for lanes still admitting prompt (INCLUDING
        the final prompt token — the fused program samples right after
        it, exactly like the decode step would), 1 for decoding lanes,
        0 for idle lanes. Paged lanes clip bulk to what the pool can
        back right now; ``_ensure_decode_blocks`` already guaranteed
        every live lane at least one backed position, so a clipped lane
        still consumes >= 1 token (never preempt for prefill)."""
        B = self.batch_size
        S = self.prefill_chunk
        lens = np.zeros((B,), np.int32)
        for i in range(B):
            if not live[i]:
                continue
            r = self._sched.slot(i).request
            lens[i] = max(1, min(S, len(r.prompt) - int(pos[i])))
            if lens[i] > 1 and self._pool is not None:
                backed = self._grow_evicting(i, int(pos[i]) + int(lens[i]))
                lens[i] = min(int(lens[i]), max(1, backed - int(pos[i])))
        return lens

    def _fused_pass(self, cache, pos: np.ndarray, live: np.ndarray,
                    tokens: np.ndarray, lens: np.ndarray, ctrl,
                    greedy_only, sub, fault):
        """One fused mixed prefill+decode launch + its bookkeeping.

        The single-launch counterpart of the chunk-pass + decode-pass
        pair: every live lane consumes its ``lens[i]`` chunk tokens and
        the lanes whose consumption reaches past their prompt (decoding
        lanes, and prompt lanes admitting their final token) emit one
        generated token, sampled inside the launch. Fault handling is
        identical to ``_decode_launch``: retry once on the bitwise-exact
        XLA arm with the same inputs (and PRNG subkey), then
        ``_ReplayNeeded``. Returns (cache, ctrl_dirty).
        """
        B = self.batch_size
        sched = self._sched
        spans = self._spans
        spans.phase("engine.inputs")
        ctoks = np.zeros((B, self.prefill_chunk), np.int32)
        n_prompt = 0
        for i in range(B):
            if not live[i]:
                continue
            r = sched.slot(i).request
            if pos[i] < len(r.prompt):   # prompt lane: feed prompt slice
                ctoks[i, : lens[i]] = r.prompt[pos[i]: pos[i] + lens[i]]
                n_prompt += int(lens[i])
            else:                        # decode lane: last emitted token
                ctoks[i, 0] = tokens[i, 0]
        d_live, d_temp, d_topk, d_topp = ctrl
        rows, n_live = B * self.prefill_chunk, int(lens.sum())
        # .copy(): argument transfers are async and pos mutates below
        t_dev = jnp.asarray(ctoks)
        p_dev = jnp.asarray(pos.copy())
        l_dev = jnp.asarray(lens)

        def run(degraded: bool):
            if greedy_only:
                prog = (self._fused_greedy_xla if degraded
                        else self._fused_greedy)
                args = (self.params, cache, t_dev, p_dev, l_dev, d_live)
            else:
                prog = self._fused_xla if degraded else self._fused
                args = (self.params, cache, t_dev, p_dev, l_dev, d_live,
                        d_temp, d_topk, d_topp, sub)
            ctx = (forced_backend("xla") if degraded
                   else contextlib.nullcontext())
            pages = self._pages_mirror()
            spans.phase("engine.dispatch", program="fused", rows=rows,
                        live=n_live)
            with ctx:
                toks, cache2, bad = prog(*args, pages=pages)
            spans.phase("engine.wait")
            bad, toks = np.asarray(bad), np.asarray(toks)
            if bool((bad & live).any()):
                raise _BadLogits("non-finite logits on a live lane")
            return toks, cache2

        degraded = self._degraded_left > 0
        try:
            if fault == "raise":
                raise FaultInjected(
                    f"injected 'raise' at launch {self._launch_no - 1}")
            out = run(degraded)
            if fault == "nan":
                raise _BadLogits(
                    f"injected 'nan' at launch {self._launch_no - 1}")
        except RuntimeError as e:   # FaultInjected / _BadLogits / XLA
            if fault is not None:
                self.metrics.on_fault(fault)
            else:
                self.metrics.on_fault(
                    "nan" if isinstance(e, _BadLogits) else "error")
            self._degraded_left = self.degrade_steps
            try:
                out = run(True)   # retry once, bitwise-exact XLA arm
            except RuntimeError:
                raise _ReplayNeeded("fused launch failed twice")
        spans.phase("engine.emit")
        if self._degraded_left > 0:
            self._degraded_left -= 1
            self.metrics.on_degraded_step()
        nxt_tok, cache = out
        t_now = self._now()
        self.metrics.on_step(
            int(live.sum()), sched.queue_depth, t_now, kind="fused",
            blocks_in_use=(None if self._pool is None
                           else self._pool.used_blocks),
            shared_blocks=(self._pool.shared_blocks()
                           if self.prefix_cache else None))
        self.metrics.on_fused_rows(rows, n_live)
        if n_prompt:
            self.metrics.on_prompt_tokens(n_prompt, kind="prefill")

        dirty = False
        for i in range(B):
            if not live[i]:
                continue
            st = sched.slot(i)
            r = st.request
            pos[i] += int(lens[i])
            st.pos = int(pos[i])
            if pos[i] < len(r.prompt):   # still admitting bulk prompt;
                # keep the next-token slot current in case the next
                # iteration falls through to the plain decode program
                tokens[i, 0] = int(r.prompt[pos[i]])
                continue
            tok = int(nxt_tok[i])
            if not r.generated:
                self.metrics.on_first_token(r.rid, t_now)
            r.generated.append(tok)
            if r.on_token is not None:
                r.on_token(r.rid, tok)
            tokens[i, 0] = tok
            if (
                len(r.generated) >= r.max_new_tokens
                or (r.eos_id is not None and tok == r.eos_id)
                or pos[i] >= self.max_len - 1   # cache cap
            ):
                self._finish(i, t_now, live, pos, tokens)
                dirty = True
        return cache, dirty

    def _spec_pass(self, cache, pos: np.ndarray, live: np.ndarray,
                   tokens: np.ndarray, ctrl, fault):
        """One speculative draft-and-verify iteration (pure-decode,
        greedy-only — the caller gates on both).

        The drafter proposes up to ``spec_k`` tokens per lane; ONE
        verify launch (M = batch * (spec_k + 1), the large-M arm) scores
        every column; greedy acceptance emits the longest matching draft
        prefix plus the verifier's own corrected/next token. Column j's
        logits are exactly what the plain 1-token walk would compute
        after consuming the same j+1 tokens (the chunked-prefill parity
        argument), so by induction over the accepted prefix the emitted
        stream is token-identical to plain decode — only launch count
        changes. Rejection rewinds the host ``pos`` vector and (paged)
        trims the lane's tail blocks; stale cache rows past the rewound
        position are harmless under the write-discipline invariant.

        Returns ``(cache, handled, fault, ctrl_dirty)``. ``handled``
        False means the caller must fall through to the plain decode
        program: either nothing could be drafted (``fault`` is handed
        back unspent) or the verify launch failed (``fault`` comes back
        None — consumed; degraded mode is set, so the plain decode
        retraces this iteration on the bitwise-exact XLA arm from the
        same cache, and its own retry/replay machinery takes over from
        there — a greedy replay recomputes the identical stream).
        """
        B = self.batch_size
        sched = self._sched
        S = self.spec_k + 1
        # per-lane draft budget: stay inside the cache cap and the
        # request's remaining token budget, and (paged) what the pool
        # can back right now — clip, never preempt (drafts must never
        # cost running work its blocks, mirroring the chunk pass)
        ks = np.zeros((B,), np.int32)
        hists: Dict[int, np.ndarray] = {}
        for i in range(B):
            if not live[i]:
                continue
            r = sched.slot(i).request
            k = min(self.spec_k,
                    self.max_len - 1 - int(pos[i]),
                    max(0, r.max_new_tokens - len(r.generated) - 1))
            if k > 0 and self._pool is not None:
                backed = self._grow_evicting(i, int(pos[i]) + k + 1)
                k = min(k, max(0, backed - int(pos[i]) - 1))
            ks[i] = k
            # the lane's consumed tokens + the pending feed token: the
            # (possibly replay-folded) prompt, then fresh generations
            folded = self._folded.get(r.rid, 0)
            seq = np.concatenate([
                np.asarray(r.prompt, np.int32),
                np.asarray(r.generated[folded:], np.int32)])
            hists[i] = seq[: int(pos[i]) + 1]
        slots = [i for i in range(B) if live[i] and ks[i] > 0]
        if not slots:
            return cache, False, fault, False
        spans = self._spans
        spans.phase("engine.inputs")
        d0 = self._drafter.launches
        try:
            drafts = self._drafter.propose(
                slots, [hists[i] for i in slots],
                [int(ks[i]) for i in slots])
        except Exception:
            self.metrics.on_spec_draft_error()
            drafts = None
        n_draft = self._drafter.launches - d0
        if n_draft:
            self.metrics.on_draft_launches(n_draft)
        if drafts is None:
            return cache, False, fault, False
        toks = np.zeros((B, S), np.int32)
        lens = np.zeros((B,), np.int32)
        for i in range(B):
            if not live[i]:
                continue
            toks[i, 0] = tokens[i, 0]
            d = np.asarray(drafts.get(i, ()), np.int32).ravel()[: int(ks[i])]
            ks[i] = len(d)
            toks[i, 1: 1 + len(d)] = d
            lens[i] = 1 + len(d)   # k == 0 lanes ride along as plain decode
        if not (ks > 0).any():
            return cache, False, fault, False

        d_live = ctrl[0]
        # .copy(): argument transfers are async and pos mutates below
        t_dev = jnp.asarray(toks)
        p_dev = jnp.asarray(pos.copy())
        l_dev = jnp.asarray(lens)
        try:
            if fault == "raise":
                raise FaultInjected(
                    f"injected 'raise' at verify launch {self._launch_no - 1}")
            pages = self._pages_mirror()
            spans.phase("engine.dispatch", program="verify")
            tgt, cache2, bad = self._verify(
                self.params, cache, t_dev, p_dev, l_dev, d_live,
                pages=pages)
            spans.phase("engine.wait")
            bad, tgt = np.asarray(bad), np.asarray(tgt)
            if bool((bad & live).any()):
                raise _BadLogits("non-finite logits on a live lane")
            if fault == "nan":
                raise _BadLogits(
                    f"injected 'nan' at verify launch {self._launch_no - 1}")
        except RuntimeError as e:   # FaultInjected / _BadLogits / XLA
            if fault is not None:
                self.metrics.on_fault(fault)
            else:
                self.metrics.on_fault(
                    "nan" if isinstance(e, _BadLogits) else "error")
            self._degraded_left = self.degrade_steps
            self.metrics.on_spec_fallback()
            # the failed launch's cache output is discarded (jitted
            # steps are functional): the plain decode below this pass
            # sees the pre-verify cache, bit-for-bit
            return cache, False, None, False

        spans.phase("engine.emit")
        t_now = self._now()
        self.metrics.on_step(
            int(live.sum()), sched.queue_depth, t_now, kind="verify",
            blocks_in_use=(None if self._pool is None
                           else self._pool.used_blocks),
            shared_blocks=(self._pool.shared_blocks()
                           if self.prefix_cache else None))

        dirty = False
        for i in range(B):
            if not live[i]:
                continue
            st = sched.slot(i)
            r = st.request
            k = int(ks[i])
            # longest draft prefix the verifier agrees with: column j's
            # argmax must equal the token fed at column j+1
            a = 0
            while a < k and tgt[i, a] == toks[i, a + 1]:
                a += 1
            if k:
                self.metrics.on_spec(k, a)
            # emit the accepted drafts plus the corrected/next token
            # sequentially, with the plain decode path's exact per-token
            # finish checks — the stream (and where it stops) is the one
            # plain decode would produce
            emitted = 0
            finished = False
            for j in range(a + 1):
                tok = int(tgt[i, j])
                if not r.generated:
                    self.metrics.on_first_token(r.rid, t_now)
                r.generated.append(tok)
                if r.on_token is not None:
                    r.on_token(r.rid, tok)
                emitted = j + 1
                if (
                    len(r.generated) >= r.max_new_tokens
                    or (r.eos_id is not None and tok == r.eos_id)
                    or int(pos[i]) + emitted >= self.max_len - 1  # cache cap
                ):
                    finished = True
                    break
            new_pos = int(pos[i]) + emitted
            pos[i] = new_pos
            st.pos = new_pos
            tokens[i, 0] = int(r.generated[-1])
            if finished:
                self._finish(i, t_now, live, pos, tokens)
                dirty = True
            elif self._pool is not None and emitted <= k:
                # rollback: the rewound host pos is authoritative; unmap
                # the lane's tail blocks past its next write row. Never
                # trims below pos+1 rows, so blocks shared at admission
                # (all within the consumed prefix) are structurally out
                # of reach — COW safety by construction, not by check.
                self._pool.trim(i, new_pos + 1)
        return cache2, True, None, dirty

    def _pages_mirror(self):
        """Device mirror of the pool's page table, refreshed only when the
        allocator mutated it (same pattern as the ctrl arrays)."""
        if self._pool is None:
            return None
        if self._pages_dev is None or self._pages_ver != self._pool.version:
            # .copy(): transfers are async and the host table mutates on
            # the very next alloc/release.
            self._pages_dev = jnp.asarray(self._pool.table.copy())
            self._pages_ver = self._pool.version
        return self._pages_dev

    def _run_continuous(self) -> Dict[int, Request]:
        B = self.batch_size
        sched = self._sched
        paged = self.kv_layout == "paged"
        # prefix-cache runs keep pool + device cache alive across run()
        # calls: retained session chains and hash-cache entries point
        # into them, which is what makes the next turn's submit->run
        # warm. Every other configuration rebuilds per run, exactly as
        # before.
        if not (self.prefix_cache and self._pool is not None):
            self._pool = (KVBlockPool(self.kv_blocks, self.kv_block_size, B,
                                      self._n_pt) if paged else None)
            self._pages_dev = None
            self._pages_ver = -1
            self._cache = make_cache(
                self.params, self.cfg, B, self.max_len, per_lane=True,
                paged=(self.kv_blocks, self.kv_block_size) if paged else None)
        cache = self._cache
        cache_bytes = sum(int(x.size) * x.dtype.itemsize
                          for x in jax.tree.leaves(cache))
        self.metrics.set_kv_stats(
            cache_bytes,
            kv_blocks=self.kv_blocks if paged else None,
            kv_block_size=self.kv_block_size if paged else None)
        # sliding-window + paged attention: the per-call arm gate
        # (models/layers._paged_attn_arm) routes any decode whose window
        # is shorter than the page-table span down the XLA gather arm —
        # silently, until now. The continuous engine only admits configs
        # with window >= max_len, so the gate can only fire in the
        # max_len <= window < n_pt * block_size rounding band; count it
        # per decode launch so the ledger makes the lost kernel visible.
        window_xla = bool(
            paged and self.cfg.sliding_window
            and self.cfg.sliding_window < self._n_pt * self.kv_block_size)
        tokens = np.zeros((B, 1), np.int32)
        pos = np.zeros((B,), np.int32)
        live = np.zeros((B,), bool)
        reset = np.zeros((B,), bool)   # lanes admitted since the last step
        temp = np.zeros((B,), np.float32)
        topk = np.zeros((B,), np.int32)
        topp = np.ones((B,), np.float32)
        ctrl = None        # device mirror of (live, temp, topk, topp):
        ctrl_dirty = True  # refreshed only on admit/finish, not per step
        greedy_only = True  # no live lane samples; refreshed with ctrl
        spans = self._spans

        while sched.has_work():
            spans.end()
            if self.on_iteration is not None:
                # service-layer hook (replica inbox drain / heartbeat /
                # kill). It may submit, cancel or raise; a raise
                # abandons the run — the supervisor discards the engine.
                self.on_iteration()
                if not sched.has_work():
                    break
            spans.begin(self._launch_no)
            now = self._now()
            if self._lifecycle_pass(now, live, pos, tokens):
                ctrl_dirty = True
                if not sched.has_work():
                    break
            while True:
                # paged: admit one at a time so the allocator-aware gate
                # sees each admission's block reservation before judging
                # the next queued request (no overcommit inside a batch).
                admitted = sched.admit(
                    now, gate=self._admit_gate if paged else None,
                    limit=1 if paged else None)
                if not admitted:
                    break
                for slot, req in admitted:
                    live[slot] = True
                    pos[slot] = 0
                    tokens[slot, 0] = int(req.prompt[0])
                    reset[slot] = True
                    sp = (req.sampling if req.sampling is not None
                          else self.sampling)
                    temp[slot], topk[slot], topp[slot] = (
                        sp.temperature, sp.top_k, sp.top_p)
                    ctrl_dirty = True
                    self.metrics.on_admit(req.rid, now)
                    if paged:
                        if self.prefix_cache:
                            # warm start: map matched blocks, COW-fork
                            # the tail, advance pos past the prefix
                            cache = self._attach_prefix(
                                slot, req, cache, pos, tokens)
                        # reserve prompt + minimum decode budget
                        self._grow_evicting(slot, self._admit_tokens(req))
                if not paged:
                    break
            if not live.any():
                nxt = sched.next_arrival()
                if nxt is None:       # nothing queued, nothing running
                    break
                self._idle_until(nxt)
                continue
            if self._chunk_step is not None:
                try:
                    cache, launched = self._prefill_chunk_pass(
                        cache, pos, live, tokens)
                except _ReplayNeeded:
                    self._replay_live_lanes(self._now(), live, pos, tokens)
                    ctrl_dirty = True
                    continue
                if launched and not any(
                    live[i] and pos[i] >= len(sched.slot(i).request.prompt) - 1
                    for i in range(B)
                ):           # pure prefill phase: every live lane still has
                    continue  # bulk, so there is nothing to decode yet.
                # Otherwise fall through and decode in the same iteration:
                # drained lanes generate while their neighbors keep
                # chunking (the decode step teacher-forces mid-bulk lanes
                # one extra prompt token — order-free per lane, so token
                # streams are unchanged; only TTFT timing improves).
                if launched:
                    spans.phase("engine.admit", launch=self._launch_no)
            if paged:
                # back every lane's next write position; exhaustion
                # preempts the youngest lane(s) into the queue.
                before = self.metrics.preemptions
                self._ensure_decode_blocks(live, pos, tokens)
                if self.metrics.preemptions != before:
                    ctrl_dirty = True
                    if not live.any():
                        continue
            # once-per-launch fault draw. An 'alloc' drill mutates the
            # live set through the standing preemption machinery, so it
            # runs *before* the ctrl refresh below; 'raise'/'nan' ride
            # into the launch helper.
            fault = (self.faults.draw(self._launch_no)
                     if self.faults is not None else None)
            self._launch_no += 1
            if fault == "alloc":
                if paged and live.any():
                    self.metrics.on_fault("alloc")
                    victim = max(
                        (j for j in range(B) if live[j]),
                        key=lambda j: sched.slot(j).seq)
                    self._preempt(victim, self._now(), live, pos, tokens)
                    ctrl_dirty = True
                    fault = None
                    if not live.any():
                        continue
                else:
                    fault = "raise"  # contiguous: no allocator to exhaust
            # per-lane token counts of a fused launch: reserves the blocks
            # of each lane's prompt chunk, so it belongs to admission
            lens = self._fused_lens(live, pos) if self.fused_step else None
            spans.phase("engine.inputs")
            if ctrl_dirty:
                ctrl = tuple(jnp.asarray(a)
                             for a in (live, temp, topk, topp))
                # greedy fast path predicate: folded into the ctrl refresh
                # (live/temp only change on admit/finish), so steady-state
                # steps skip the host-array scan.
                greedy_only = not (temp[live] > 0.0).any()
                ctrl_dirty = False

            sub = None
            if not greedy_only:   # greedy fast path: no sampler, no PRNG
                # one split per iteration, shared by every retry of this
                # launch — a degraded retry redraws identical samples
                self._key, sub = jax.random.split(self._key)
            if (self._drafter is not None and greedy_only
                    and self._degraded_left == 0
                    and not any(
                        live[i]
                        and pos[i] < len(sched.slot(i).request.prompt) - 1
                        for i in range(B))):
                # speculative iteration: greedy-only (a sampled stream
                # has no acceptance identity), pure-decode steady state
                # only (drafts never preempt prefill — a lane still
                # admitting bulk prompt sends the whole batch down the
                # plain path), and never while degraded (the XLA
                # fallback arm should drain its countdown on the plain
                # 1-token program the recovery path reasons about)
                cache, handled, fault, dirty = self._spec_pass(
                    cache, pos, live, tokens, ctrl, fault)
                if handled:
                    ctrl_dirty |= dirty
                    continue
            if self.fused_step:
                if (lens > 1).any():
                    # at least one lane still has bulk prompt: this whole
                    # mixed iteration is ONE fused launch (chunk admission
                    # + the decode token + sampling in the same program)
                    try:
                        cache, dirty = self._fused_pass(
                            cache, pos, live, tokens, lens, ctrl,
                            greedy_only, sub, fault)
                    except _ReplayNeeded:
                        self._replay_live_lanes(
                            self._now(), live, pos, tokens)
                        ctrl_dirty = True
                        continue
                    ctrl_dirty |= dirty
                    continue
                # every live lane is one token from emitting: fall through
                # to the plain decode program (identical S=1 math)
            # trailing step args shared by both step variants: page-table
            # mirror (paged) and recurrent lane-reset mask (ssm/hybrid)
            extra = dict(pages=self._pages_mirror())
            if self._needs_reset:
                extra["reset"] = jnp.asarray(reset.copy())
            try:
                nxt_tok, cache = self._decode_launch(
                    cache, tokens, pos, ctrl, greedy_only, sub, extra,
                    live, fault)
            except _ReplayNeeded:
                self._replay_live_lanes(self._now(), live, pos, tokens)
                ctrl_dirty = True
                continue
            reset[:] = False    # consumed by this launch
            t_now = self._now()
            self.metrics.on_step(
                int(live.sum()), sched.queue_depth, t_now,
                blocks_in_use=(None if self._pool is None
                               else self._pool.used_blocks),
                shared_blocks=(self._pool.shared_blocks()
                               if self.prefix_cache else None))
            if window_xla:
                self.metrics.on_window_fallback()

            n_prompt = 0
            for i in range(B):
                if not live[i]:
                    continue
                st = sched.slot(i)
                r = st.request
                pos[i] += 1
                st.pos = int(pos[i])
                if pos[i] < len(r.prompt):      # still teacher-forcing; an
                    tokens[i, 0] = int(r.prompt[pos[i]])  # eos_id inside the
                    n_prompt += 1               # prompt never ends the lane
                    continue
                tok = int(nxt_tok[i])
                if not r.generated:
                    self.metrics.on_first_token(r.rid, t_now)
                r.generated.append(tok)
                if r.on_token is not None:
                    r.on_token(r.rid, tok)
                tokens[i, 0] = tok
                if (
                    len(r.generated) >= r.max_new_tokens
                    or (r.eos_id is not None and tok == r.eos_id)
                    or pos[i] >= self.max_len - 1   # cache cap
                ):
                    self._finish(i, t_now, live, pos, tokens)
                    ctrl_dirty = True
            if n_prompt:
                self.metrics.on_prompt_tokens(n_prompt)
        spans.end()
        if self.prefix_cache:
            self._cache = cache   # retained chains point into it
            self.metrics.set_session_stats(len(self._sessions))
        else:
            self._cache = None    # per-run cache, freed as before
        return self.completed

    # ------------------------------------------------------------------
    # legacy wave mode (parity baseline)
    # ------------------------------------------------------------------

    def _run_wave_batch(self, wave: List[Request]) -> None:
        B = self.batch_size
        cache = make_cache(self.params, self.cfg, B, self.max_len)
        pos = 0
        done = [False] * len(wave)
        emitted_first = [False] * len(wave)
        # lane i consumes prompt[pos] while pos < len(prompt)-1, then its
        # generated stream. First fed token is prompt[0].
        tokens = np.zeros((B, 1), np.int32)
        for i, r in enumerate(wave):
            tokens[i, 0] = int(r.prompt[0])

        while not all(done) and pos < self.max_len - 1:
            logits, cache = self._decode(
                self.params, cache, jnp.asarray(tokens),
                jnp.asarray(pos, jnp.int32),
            )
            nxt = np.asarray(jnp.argmax(logits, axis=-1))
            pos += 1
            t_now = self._now()
            self.metrics.on_step(
                sum(not d for d in done), self._sched.queue_depth, t_now)
            n_prompt = 0
            for i, r in enumerate(wave):
                if done[i]:
                    continue
                if pos < len(r.prompt):            # still teacher-forcing
                    tokens[i, 0] = int(r.prompt[pos])
                    n_prompt += 1
                else:                               # generating
                    tok = int(nxt[i])
                    if not emitted_first[i]:
                        emitted_first[i] = True
                        self.metrics.on_first_token(r.rid, t_now)
                    r.generated.append(tok)
                    if r.on_token is not None:
                        r.on_token(r.rid, tok)
                    tokens[i, 0] = tok
                    if (
                        len(r.generated) >= r.max_new_tokens
                        or (r.eos_id is not None and tok == r.eos_id)
                    ):
                        done[i] = True
                        r.status = "ok"
                        self.metrics.on_finish(r.rid, t_now, len(r.generated))
                        self.completed[r.rid] = r
            if n_prompt:
                self.metrics.on_prompt_tokens(n_prompt)
        for i, r in enumerate(wave):                # max_len cutoff
            if not done[i]:
                r.status = "ok"
                self.metrics.on_finish(r.rid, self._now(), len(r.generated))
                self.completed[r.rid] = r

    def _run_wave(self) -> Dict[int, Request]:
        while True:
            admitted = self._sched.admit()   # legacy: ignores arrival times
            if not admitted:
                break
            now = self._now()
            for _, req in admitted:
                self.metrics.on_admit(req.rid, now)
            self._run_wave_batch([req for _, req in admitted])
            for slot, _ in admitted:
                self._sched.release(slot)
        return self.completed

    # ------------------------------------------------------------------
    def check_shutdown_invariants(self) -> None:
        """Post-``run()`` leak check (tests and benches call this after
        every run, fault storms included). Asserts that:

          * the scheduler is fully drained — no occupied slots, no
            queued requests;
          * the paged block pool (if any) has refcounts exactly
            explained by the page tables plus the prefix-cache /
            session holdings, refcount==0 ⇔ on the free list, and
            conservation holds (``KVBlockPool.check_invariants``);
          * with no prefix cache, every block is back on the free list;
            with one, every used block is accounted to a cached chain
            or retained session (no leaked shared blocks) and no
            session still claims an in-flight request;
          * every submitted rid is in ``completed`` exactly once, each
            with a typed terminal status.

        Raises AssertionError on the first violated invariant.
        """
        sched = self._sched
        assert sched.occupancy == 0, (
            f"{sched.occupancy} slot(s) still occupied after run()")
        assert sched.queue_depth == 0, (
            f"{sched.queue_depth} request(s) still queued after run()")
        if self._pool is not None:
            ext: Dict[int, int] = {}
            for holder in (self._prefix, self._sessions):
                if holder is not None:
                    for b, n in holder.holdings().items():
                        ext[b] = ext.get(b, 0) + n
            self._pool.check_invariants(external=ext)
            assert self._pool.used_blocks == len(ext), (
                f"{self._pool.used_blocks} KV block(s) in use after run() "
                f"but only {len(ext)} accounted to cached chains / "
                f"retained sessions")
            assert not self._pending_match, (
                f"pinned prefix matches never attached: "
                f"{sorted(self._pending_match)}")
            assert not self._session_rid, (
                f"sessions still claim in-flight requests: "
                f"{sorted(self._session_rid)}")
        submitted = set(self.metrics.requests)
        finished = set(self.completed)
        assert submitted == finished, (
            f"submitted/completed rid mismatch: "
            f"missing={sorted(submitted - finished)} "
            f"extra={sorted(finished - submitted)}")
        from repro.serving.scheduler import STATUSES
        for r in self.completed.values():
            assert r.status in STATUSES, (
                f"request {r.rid} finished without a typed status "
                f"({r.status!r})")
        assert not self._cancel_pending, (
            f"cancellations never resolved: {sorted(self._cancel_pending)}")

    def now(self) -> float:
        """Current time on the engine clock (what ``arrival_time``,
        deadlines and session TTLs are measured against). Multi-turn
        drivers stamp follow-up submissions with this so queue-wait and
        TTFT stay meaningful across run() calls."""
        return self._now()

    def clear_prefix_cache(self) -> int:
        """Drop every cached chain and retained session, returning their
        pinned blocks to the pool. Returns the number of blocks freed.
        After this (and outside a run), a prefix-cache engine's pool is
        fully free — the teardown counterpart of
        ``check_shutdown_invariants``."""
        if self._pool is None:
            return 0
        before = self._pool.free_blocks
        if self._prefix is not None:
            self._prefix.clear(self._pool)
        if self._sessions is not None:
            self._sessions.clear(self._pool)
        return self._pool.free_blocks - before

    def run(self) -> Dict[int, Request]:
        if self.mode == "continuous":
            return self._run_continuous()
        return self._run_wave()
