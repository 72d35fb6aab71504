"""Serving metrics: per-request latency plus engine-level utilization.

The collector is clock-agnostic — the engine stamps every event with its
own clock (wall time by default, a virtual clock in simulation) so the
numbers stay meaningful either way:

  * per request: queue wait (arrival -> admit), TTFT (arrival -> first
    *generated* token, i.e. prompt walk included), decode tokens/s, the
    terminal ``status`` (ok|timeout|expired|cancelled|rejected|failed),
    and how many times the request was preempted and requeued;
  * per engine run: aggregate generated tokens/s over the active window,
    mean slot occupancy and queue depth sampled once per step, the
    prefill-vs-decode token split (plus fused prefill+decode launches,
    the token rows they computed and the rows that were live, and the
    total launch count), the paged-KV footprint (cache bytes, pool
    geometry, preemptions, blocks-in-use), and the
    **fault-tolerance ledger**: timeouts / cancellations / expired /
    sheds / failed terminal counts, injected-or-detected fault count by
    kind, degraded-mode steps (launches retried or pinned to the
    bitwise-exact XLA arm) and replay events (lanes preempted and
    requeued by the recovery path);
  * a **step-time watchdog** (``StepTimeWatchdog``): per-iteration wall
    time fed through the EWMA logic of ``runtime/straggler.py``,
    exposing p50/p95 step time and a ``stalled`` flag whenever an
    iteration exceeds ``threshold x`` the EWMA of its predecessors.
"""
from __future__ import annotations

import dataclasses
import threading
from typing import Dict, List, Optional

from repro.runtime.straggler import StragglerMonitor


@dataclasses.dataclass
class RequestMetrics:
    rid: int
    arrival_time: float = 0.0
    prompt_len: int = 0
    admit_time: Optional[float] = None
    first_token_time: Optional[float] = None
    finish_time: Optional[float] = None
    n_generated: int = 0
    n_preempted: int = 0    # times this request was preempted + requeued
    status: Optional[str] = None   # terminal status (scheduler.STATUSES)

    @property
    def queue_wait(self) -> Optional[float]:
        if self.admit_time is None:
            return None
        return self.admit_time - self.arrival_time

    @property
    def ttft(self) -> Optional[float]:
        """Arrival to first generated token (prompt processing included)."""
        if self.first_token_time is None:
            return None
        return self.first_token_time - self.arrival_time

    @property
    def decode_tokens_per_s(self) -> Optional[float]:
        if self.finish_time is None or self.first_token_time is None:
            return None
        span = self.finish_time - self.first_token_time
        if span <= 0:  # single-token request: no measurable decode span
            return None
        return (self.n_generated - 1) / span


def _percentile(xs: List[float], q: float) -> float:
    ys = sorted(xs)
    if not ys:
        return float("nan")
    i = min(len(ys) - 1, max(0, round(q * (len(ys) - 1))))
    return ys[i]


class StepTimeWatchdog:
    """EWMA step-time monitor for one engine run.

    Reuses the smoothing from ``runtime.straggler.StragglerMonitor``
    (one 'host' = this engine): each recorded iteration time updates the
    EWMA, and an iteration is flagged **stalled** when it exceeds
    ``threshold x`` the EWMA of the iterations before it (after
    ``warmup`` samples — the first steps include compilation). A
    virtual-clock run records dt = 0 everywhere and never flags.
    """

    def __init__(self, alpha: float = 0.2, threshold: float = 3.0,
                 warmup: int = 3):
        self._mon = StragglerMonitor(1, alpha=alpha, threshold=threshold,
                                     warmup=warmup)
        self.threshold = threshold
        self.warmup = warmup
        self.samples: List[float] = []
        self.stalled = False          # the most recent iteration stalled
        self.stalled_steps = 0        # iterations flagged over the run

    def record(self, dt: float) -> bool:
        """Feed one iteration wall time; returns the stalled flag."""
        prev = self._mon.ewma(0)
        self.stalled = bool(
            self._mon.count(0) >= self.warmup
            and prev is not None and prev > 0.0
            and dt > self.threshold * prev
        )
        if self.stalled:
            self.stalled_steps += 1
        self._mon.record(0, dt)
        self.samples.append(dt)
        return self.stalled

    @property
    def ewma(self) -> Optional[float]:
        return self._mon.ewma(0)

    def p(self, q: float) -> float:
        return _percentile(self.samples, q)


#: terminal-status -> collector counter attribute
_STATUS_COUNTERS = {
    "timeout": "timeouts",
    "expired": "expired",
    "cancelled": "cancellations",
    "rejected": "sheds",
    "failed": "failed",
}


class MetricsCollector:
    """Event sink for one engine run."""

    def __init__(self, watchdog: Optional[StepTimeWatchdog] = None):
        self.requests: Dict[int, RequestMetrics] = {}
        self.occupancy_samples: List[int] = []
        self.queue_depth_samples: List[int] = []
        self.start_time: Optional[float] = None
        self.end_time: Optional[float] = None
        # prefill-vs-decode split (chunked prefill observability)
        self.prefill_steps: int = 0          # chunk-program launches
        self.decode_steps: int = 0           # decode-program launches
        self.fused_steps: int = 0            # fused prefill+decode launches
        self.fused_rows: int = 0             # rows those launches computed
        self.fused_rows_live: int = 0        # of which consumed a token
        self.prefill_tokens: int = 0         # prompt tokens via chunk program
        self.prompt_decode_tokens: int = 0   # prompt tokens walked 1/step
        # speculative-decode ledger (serving/spec_decode.py). Tokens/s
        # and per-request counts stay accepted-only under speculation by
        # construction: rejected drafts never enter ``generated``, so
        # ``n_generated`` (and every rate derived from it) never double-
        # counts a proposed-but-refused token.
        self.verify_steps: int = 0           # verify-program launches
        self.draft_launches: int = 0         # drafter device launches
        self.spec_proposed: int = 0          # draft tokens proposed
        self.spec_accepted: int = 0          # draft tokens accepted
        self.spec_lanes: int = 0             # lane-iterations speculated
        self.accept_hist: Dict[int, int] = {}   # accepted-length histogram
        self.spec_draft_errors: int = 0      # drafter raised; plain decode
        self.spec_fallbacks: int = 0         # verify faulted; plain decode
        # sliding-window + paged attention: decode launches the per-call
        # arm gate routed down the XLA gather arm because the window is
        # shorter than the page-table span (docs/ENV.md, ICQ_PAGED_ATTN)
        self.window_fallbacks: int = 0
        # paged-KV observability (kv_layout='paged')
        self.preemptions: int = 0            # preempt-and-requeue events
        self.blocks_in_use_samples: List[int] = []   # sampled once per step
        self.cache_bytes: Optional[int] = None       # device KV cache bytes
        self.kv_blocks: Optional[int] = None         # pool size (blocks)
        self.kv_block_size: Optional[int] = None     # rows per block
        # prefix-cache / session observability (serving/prefix_cache.py)
        self.prefix_lookups: int = 0         # admissions that consulted it
        self.prefix_hits: int = 0            # admissions with matched > 0
        self.prefix_tokens_skipped: int = 0  # prompt tokens never prefilled
        self.prefix_inserts: int = 0         # new hash-cache entries
        self.prefix_evictions: int = 0       # hash-cache entries evicted
        self.cow_forks: int = 0              # partial tail blocks COW-forked
        self.session_hits: int = 0           # hits matched via a session chain
        self.session_expiries: int = 0       # sessions dropped by TTL
        self.session_evictions: int = 0      # sessions dropped by pool pressure
        self.sessions_active: int = 0        # retained sessions at run end
        self.shared_blocks_samples: List[int] = []  # sampled once per step
        # fault-tolerance ledger
        self.timeouts: int = 0               # running lanes past deadline_s
        self.expired: int = 0                # queued requests past their wait
        self.cancellations: int = 0          # cancel(rid) taking effect
        self.sheds: int = 0                  # bounded-queue rejections
        self.failed: int = 0                 # recovery gave up on the request
        self.faults: Dict[str, int] = {}     # injected/detected, by kind
        self.degraded_steps: int = 0         # launches on the XLA fallback arm
        self.replays: int = 0                # whole-batch replay events
        self.watchdog = watchdog if watchdog is not None else StepTimeWatchdog()

    # -- events ---------------------------------------------------------
    def on_submit(self, rid: int, arrival_time: float, prompt_len: int):
        self.requests[rid] = RequestMetrics(
            rid=rid, arrival_time=arrival_time, prompt_len=prompt_len)

    def on_admit(self, rid: int, t: float):
        self.requests[rid].admit_time = t

    def on_first_token(self, rid: int, t: float):
        self.requests[rid].first_token_time = t

    def on_finish(self, rid: int, t: float, n_generated: int,
                  status: str = "ok"):
        r = self.requests[rid]
        r.finish_time = t
        r.n_generated = n_generated
        r.status = status
        counter = _STATUS_COUNTERS.get(status)
        if counter is not None:
            setattr(self, counter, getattr(self, counter) + 1)

    def on_step(self, occupancy: int, queue_depth: int, t: float,
                kind: str = "decode", blocks_in_use: Optional[int] = None,
                shared_blocks: Optional[int] = None):
        if self.start_time is None:
            self.start_time = t
        elif self.end_time is not None:
            self.watchdog.record(max(0.0, t - self.end_time))
        self.end_time = t
        self.occupancy_samples.append(occupancy)
        self.queue_depth_samples.append(queue_depth)
        if blocks_in_use is not None:
            self.blocks_in_use_samples.append(blocks_in_use)
        if shared_blocks is not None:
            self.shared_blocks_samples.append(shared_blocks)
        if kind == "prefill":
            self.prefill_steps += 1
        elif kind == "fused":
            self.fused_steps += 1
        elif kind == "verify":
            self.verify_steps += 1
        else:
            self.decode_steps += 1

    def on_fused_rows(self, rows: int, live: int):
        """One fused launch's token rows: ``rows`` = batch x chunk, what
        the program computes whatever the lanes hold; ``live`` = the
        rows that consumed a prompt or decode token (its ``lens.sum()``).
        Their ratio is the share of the fused step's work that is used."""
        self.fused_rows += int(rows)
        self.fused_rows_live += int(live)

    def on_preempt(self, rid: int, t: float):
        """Lane preempted (pool exhausted / replay) + request requeued."""
        self.preemptions += 1
        self.requests[rid].n_preempted += 1

    def on_fault(self, kind: str):
        """A launch fault was injected or detected (kind: 'raise' | 'nan'
        | 'alloc' | 'error')."""
        self.faults[kind] = self.faults.get(kind, 0) + 1

    def on_degraded_step(self):
        """One launch executed on the degraded (bitwise-exact XLA) arm."""
        self.degraded_steps += 1

    def on_replay(self):
        """Recovery preempted the live lanes and requeued them for replay."""
        self.replays += 1

    def on_spec(self, proposed: int, accepted: int):
        """One lane's draft-and-verify outcome this iteration:
        ``proposed`` draft tokens went into the verify launch, the first
        ``accepted`` of them matched the verifier's greedy verdict (the
        lane then also emitted the verifier's corrected/next token, so
        it advanced ``accepted + 1`` tokens for one verify launch)."""
        self.spec_lanes += 1
        self.spec_proposed += int(proposed)
        self.spec_accepted += int(accepted)
        self.accept_hist[int(accepted)] = \
            self.accept_hist.get(int(accepted), 0) + 1

    def on_draft_launches(self, n: int):
        """Device launches the drafter spent this iteration (0 for
        host-only drafters like 'ngram'/'reject')."""
        self.draft_launches += int(n)

    def on_spec_draft_error(self):
        """The drafter raised; the iteration fell back to plain decode."""
        self.spec_draft_errors += 1

    def on_spec_fallback(self):
        """The verify launch faulted (injected or genuine); the iteration
        degraded to the plain decode program, which re-emits this step's
        token(s) identically on the XLA arm."""
        self.spec_fallbacks += 1

    def on_window_fallback(self):
        """One paged decode launch ran on the XLA gather arm because the
        config's sliding window is shorter than the page-table span
        (models/layers._paged_attn_arm gate)."""
        self.window_fallbacks += 1

    def on_prefix_attach(self, matched_tokens: int, forked: bool = False,
                         via_session: bool = False):
        """One prefix-cache consultation at admission: ``matched_tokens``
        prompt tokens were warm-started from shared blocks (0 = miss),
        ``forked`` when the partial tail block was COW-forked,
        ``via_session`` when the winning match came from a session chain
        rather than the hash cache."""
        self.prefix_lookups += 1
        if matched_tokens > 0:
            self.prefix_hits += 1
            self.prefix_tokens_skipped += int(matched_tokens)
            if via_session:
                self.session_hits += 1
        if forked:
            self.cow_forks += 1

    def on_prefix_insert(self, n_entries: int):
        """New hash-cache entries indexed from a finished chain."""
        self.prefix_inserts += int(n_entries)

    def on_prefix_evictions(self, n_entries: int):
        """Hash-cache entries evicted under pool pressure."""
        self.prefix_evictions += int(n_entries)

    def on_session_expired(self, n: int):
        """Sessions dropped by the TTL sweep (``ICQ_SESSION_TTL``)."""
        self.session_expiries += int(n)

    def on_session_evicted(self, n: int):
        """Sessions dropped LRU-first under pool pressure."""
        self.session_evictions += int(n)

    def set_session_stats(self, active: int):
        """Retained sessions at run end (set by the engine per run)."""
        self.sessions_active = int(active)

    def set_kv_stats(self, cache_bytes: int,
                     kv_blocks: Optional[int] = None,
                     kv_block_size: Optional[int] = None):
        """Device KV-cache footprint for this run (set once, at cache
        build time; kv_blocks/kv_block_size only for the paged layout)."""
        self.cache_bytes = int(cache_bytes)
        self.kv_blocks = kv_blocks
        self.kv_block_size = kv_block_size

    def on_prompt_tokens(self, n: int, kind: str = "decode"):
        """Prompt tokens consumed this step: ``kind='prefill'`` via the
        S-token chunk program, ``'decode'`` teacher-forced 1/step."""
        if kind == "prefill":
            self.prefill_tokens += n
        else:
            self.prompt_decode_tokens += n

    # -- report ---------------------------------------------------------
    def status_counts(self) -> Dict[str, int]:
        """Terminal-status histogram over all finished requests."""
        out: Dict[str, int] = {}
        for r in self.requests.values():
            if r.status is not None:
                out[r.status] = out.get(r.status, 0) + 1
        return out

    def summary(self) -> Dict[str, float]:
        done = [r for r in self.requests.values() if r.finish_time is not None]
        served = [r for r in done if r.status in (None, "ok", "timeout")]
        total_tokens = sum(r.n_generated for r in done)
        wall = (
            (self.end_time - self.start_time)
            if self.start_time is not None and self.end_time is not None
            else 0.0
        )
        ttfts = [r.ttft for r in served if r.ttft is not None]
        waits = [r.queue_wait for r in served if r.queue_wait is not None]
        occ = self.occupancy_samples
        qd = self.queue_depth_samples
        bu = self.blocks_in_use_samples
        sb = self.shared_blocks_samples
        wd = self.watchdog
        return dict(
            requests=float(len(self.requests)),
            completed=float(len(done)),
            generated_tokens=float(total_tokens),
            wall_s=wall,
            tokens_per_s=(total_tokens / wall) if wall > 0 else float("nan"),
            steps=float(len(occ)),
            mean_occupancy=(sum(occ) / len(occ)) if occ else 0.0,
            mean_queue_depth=(sum(qd) / len(qd)) if qd else 0.0,
            ttft_mean=(sum(ttfts) / len(ttfts)) if ttfts else float("nan"),
            ttft_p50=_percentile(ttfts, 0.50),
            ttft_p95=_percentile(ttfts, 0.95),
            queue_wait_mean=(sum(waits) / len(waits)) if waits else 0.0,
            prefill_steps=float(self.prefill_steps),
            decode_steps=float(self.decode_steps),
            fused_steps=float(self.fused_steps),
            fused_rows=float(self.fused_rows),
            fused_rows_live=float(self.fused_rows_live),
            verify_steps=float(self.verify_steps),
            draft_launches=float(self.draft_launches),
            launches=float(self.prefill_steps + self.decode_steps
                           + self.fused_steps + self.verify_steps
                           + self.draft_launches),
            # speculative-decode ledger (accepted-only token accounting)
            spec_proposed=float(self.spec_proposed),
            spec_accepted=float(self.spec_accepted),
            spec_accept_rate=(self.spec_accepted / self.spec_proposed
                              if self.spec_proposed else float("nan")),
            mean_accept_len=(self.spec_accepted / self.spec_lanes
                             if self.spec_lanes else float("nan")),
            spec_draft_errors=float(self.spec_draft_errors),
            spec_fallbacks=float(self.spec_fallbacks),
            paged_attn_window_fallbacks=float(self.window_fallbacks),
            prefill_tokens=float(self.prefill_tokens),
            prompt_decode_tokens=float(self.prompt_decode_tokens),
            preemptions=float(self.preemptions),
            cache_bytes=(float(self.cache_bytes)
                         if self.cache_bytes is not None else float("nan")),
            kv_blocks=(float(self.kv_blocks)
                       if self.kv_blocks is not None else float("nan")),
            kv_block_size=(float(self.kv_block_size)
                           if self.kv_block_size is not None
                           else float("nan")),
            mean_blocks_in_use=((sum(bu) / len(bu)) if bu else float("nan")),
            peak_blocks_in_use=(float(max(bu)) if bu else float("nan")),
            mean_block_utilization=(
                (sum(bu) / len(bu)) / self.kv_blocks
                if bu and self.kv_blocks else float("nan")),
            # prefix-cache / session ledger
            prefix_lookups=float(self.prefix_lookups),
            prefix_hits=float(self.prefix_hits),
            prefix_hit_rate=(self.prefix_hits / self.prefix_lookups
                             if self.prefix_lookups else float("nan")),
            prefix_tokens_skipped=float(self.prefix_tokens_skipped),
            prefix_inserts=float(self.prefix_inserts),
            prefix_evictions=float(self.prefix_evictions),
            cow_forks=float(self.cow_forks),
            session_hits=float(self.session_hits),
            session_expiries=float(self.session_expiries),
            session_evictions=float(self.session_evictions),
            sessions_active=float(self.sessions_active),
            mean_shared_blocks=((sum(sb) / len(sb)) if sb else float("nan")),
            peak_shared_blocks=(float(max(sb)) if sb else float("nan")),
            # fault-tolerance ledger
            timeouts=float(self.timeouts),
            expired=float(self.expired),
            cancellations=float(self.cancellations),
            sheds=float(self.sheds),
            failed=float(self.failed),
            faults=float(sum(self.faults.values())),
            degraded_steps=float(self.degraded_steps),
            replays=float(self.replays),
            # step-time watchdog
            step_time_p50=wd.p(0.50),
            step_time_p95=wd.p(0.95),
            step_time_ewma=(wd.ewma if wd.ewma is not None else float("nan")),
            stalled_steps=float(wd.stalled_steps),
            stalled=float(wd.stalled),
        )


class ServiceMetrics:
    """Counters for the service layer above the engine (frontend ->
    router -> replicas): submissions and terminal statuses as the router
    sees them, failovers and replica restarts, frontend backpressure
    sheds, client retries, and gauges for the frontend queue depth and
    the worst replica heartbeat age. One instance is shared by every
    component of a ``ServingService``; all methods are thread-safe (the
    frontend event loop, the supervisor and N replica workers all
    report into it)."""

    def __init__(self):
        self._lock = threading.Lock()
        self.submits = 0              # requests the router accepted
        self.status_counts: Dict[str, int] = {}   # terminal statuses
        self.tokens_streamed = 0      # tokens forwarded to callers
        self.failovers = 0            # in-flight requests moved off a
        #                               dead replica (or force-failed)
        self.replica_restarts = 0     # dead replicas rebuilt + restarted
        self.replica_kills = 0        # hard kills (chaos or watchdog)
        self.frontend_sheds = 0       # submits refused by backpressure
        self.retries = 0              # client-side retry attempts
        self.duplicate_terminals = 0  # MUST stay 0: a second terminal
        #                               for an already-finished rid
        self.wal_replayed = 0         # requests re-submitted from WAL
        self.peak_pending = 0         # frontend queue-depth high water
        self.heartbeat_age_max = 0.0  # worst replica heartbeat age seen

    def _bump(self, attr: str, n: int = 1):
        with self._lock:
            setattr(self, attr, getattr(self, attr) + n)

    def on_submit(self):
        self._bump("submits")

    def on_terminal(self, status: str):
        with self._lock:
            self.status_counts[status] = self.status_counts.get(status, 0) + 1

    def on_token(self):
        self._bump("tokens_streamed")

    def on_failover(self):
        self._bump("failovers")

    def on_replica_restart(self):
        self._bump("replica_restarts")

    def on_replica_kill(self):
        self._bump("replica_kills")

    def on_shed(self):
        self._bump("frontend_sheds")

    def on_retry(self, n: int = 1):
        self._bump("retries", n)

    def on_duplicate_terminal(self):
        self._bump("duplicate_terminals")

    def on_wal_replayed(self, n: int):
        self._bump("wal_replayed", n)

    def sample(self, pending: int, heartbeat_age: float):
        """Gauge sample: current frontend queue depth + worst replica
        heartbeat age (taken by the supervisor each pass)."""
        with self._lock:
            self.peak_pending = max(self.peak_pending, int(pending))
            self.heartbeat_age_max = max(self.heartbeat_age_max,
                                         float(heartbeat_age))

    def completed(self) -> int:
        with self._lock:
            return sum(self.status_counts.values())

    def summary(self) -> Dict[str, float]:
        with self._lock:
            out = dict(
                submits=float(self.submits),
                completed=float(sum(self.status_counts.values())),
                tokens_streamed=float(self.tokens_streamed),
                failovers=float(self.failovers),
                replica_restarts=float(self.replica_restarts),
                replica_kills=float(self.replica_kills),
                frontend_sheds=float(self.frontend_sheds),
                retries=float(self.retries),
                duplicate_terminals=float(self.duplicate_terminals),
                wal_replayed=float(self.wal_replayed),
                peak_pending=float(self.peak_pending),
                heartbeat_age_max=float(self.heartbeat_age_max),
            )
            for s, n in self.status_counts.items():
                out[f"status_{s}"] = float(n)
            return out


__all__ = ["RequestMetrics", "MetricsCollector", "StepTimeWatchdog",
           "ServiceMetrics"]
