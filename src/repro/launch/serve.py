"""Serving driver: train briefly, optionally ICQuant the weights, then
serve a queue of requests through the GenerationEngine.

``python -m repro.launch.serve --arch <id> [--bits 3] [--requests 8]``

Request length policy: a request needs ``len(prompt) + max_new_tokens``
cache positions. Requests whose *prompt* cannot fit ``--max-len`` are
rejected up front; requests whose prompt fits but whose token budget
overflows the cache are truncated to the remaining budget with a
warning (``--strict-len`` rejects those too instead of truncating).
"""
from __future__ import annotations

import argparse
import os
import signal
import tempfile

import numpy as np

from repro.configs import get_config, smoke_variant
from repro.launch.compile_cache import use_compile_cache
from repro.launch.quantize import quantize_tree
from repro.launch.train import train
from repro.serving import GenerationEngine, Request, SamplingParams
from repro.serving.faults import FaultInjector, parse_fault_plan


def _install_engine_signals(engine) -> None:
    """Graceful drain on SIGINT/SIGTERM (in-process engine path): the
    first signal refuses new admissions and lets in-flight lanes finish
    with their usual typed statuses; a second signal cancels everything
    still pending (typed 'cancelled'). Either way the final
    status-count ledger prints with exactly one status per rid."""
    state = {"n": 0}

    def handler(signum, frame):
        state["n"] += 1
        if state["n"] == 1:
            print(f"[serve] signal {signum}: draining (no new admissions; "
                  f"in-flight lanes finish)", flush=True)
            engine.request_drain()
        else:
            print(f"[serve] signal {signum}: cancelling pending requests",
                  flush=True)
            for rid in list(engine.metrics.requests):
                if rid not in engine.completed:
                    try:
                        engine.cancel(rid)
                    except KeyError:
                        pass

    signal.signal(signal.SIGINT, handler)
    signal.signal(signal.SIGTERM, handler)


def _install_service_signals(svc) -> None:
    """Same drain contract for the replica service: first signal drains
    (frontend refuses submits, replicas finish in-flight work, WAL
    records go terminal), second cancels everything still pending."""
    state = {"n": 0}

    def handler(signum, frame):
        state["n"] += 1
        if state["n"] == 1:
            print(f"[serve] signal {signum}: draining (no new admissions; "
                  f"in-flight lanes finish)", flush=True)
            svc.begin_drain()
        else:
            print(f"[serve] signal {signum}: cancelling pending requests",
                  flush=True)
            for rid, (status, _) in svc.router.results().items():
                if status is None:
                    try:
                        svc.router.cancel(rid)
                    except KeyError:
                        pass

    signal.signal(signal.SIGINT, handler)
    signal.signal(signal.SIGTERM, handler)


def _serve_replicas(args, params, cfg, sampling):
    """Replica-service path (--replicas N): WAL + N supervised engine
    replicas + router + TCP frontend, driven through the retrying
    client — the full resilient-serving stack end to end."""
    from repro.serving import (FrontendUnavailable, RequestRejected,
                               ServingClient, ServingService)
    from repro.serving.wal import default_wal_path

    if args.sessions:
        raise SystemExit("[serve] the --sessions workload is in-process "
                         "only; drop --replicas")
    if args.mode == "wave":
        raise SystemExit("[serve] --replicas requires the continuous "
                         "engine; drop --mode wave")
    if args.temperature > 0:
        raise SystemExit("[serve] the TCP frontend serves greedy requests; "
                         "drop --temperature")

    def factory():
        faults = None
        if args.fault_plan is not None or args.fault_rate is not None:
            # one injector per engine: a restarted replica gets a fresh
            # (deterministic) schedule, not a half-consumed one
            faults = FaultInjector(
                parse_fault_plan(args.fault_plan) if args.fault_plan
                else None,
                seed=args.fault_seed if args.fault_seed is not None else 0,
                rate=args.fault_rate if args.fault_rate is not None else 0.0)
        return GenerationEngine(
            params, cfg, batch_size=args.batch, max_len=args.max_len,
            weight_cache=args.weight_cache, runtime_fmt=args.runtime_fmt,
            mode="continuous", sampling=sampling, seed=args.seed,
            prefill_chunk=args.prefill_chunk, kv_layout=args.kv_layout,
            kv_block_size=args.kv_block_size, kv_blocks=args.kv_blocks,
            max_queue=args.max_queue, shed_policy=args.shed_policy,
            faults=faults, degrade_steps=args.degrade_steps,
            prefix_cache=args.prefix_cache, session_ttl=args.session_ttl,
            spec_decode=args.spec_decode, spec_k=args.spec_k,
            spec_draft=args.draft)

    wal_path = args.wal if args.wal is not None else default_wal_path()
    svc = ServingService(factory, n_replicas=args.replicas,
                         wal_path=wal_path, max_pending=args.max_pending,
                         supervise_s=0.05)
    host, port = svc.start()
    _install_service_signals(svc)
    print(f"[serve] frontend: {args.replicas} replicas on {host}:{port}"
          + (f", wal={wal_path}" if wal_path else ""))
    if svc.replayed:
        print(f"[serve] WAL replay: {svc.replayed} unfinished request(s) "
              f"resubmitted")

    if args.kill_replica:
        idx, after = args.kill_replica.split(":")
        name, threshold = f"r{int(idx)}", int(after)
        fired = [False]

        def trigger(rid, tok):
            if not fired[0] and svc.metrics.tokens_streamed >= threshold:
                fired[0] = True
                print(f"[serve] KILL {name} after {threshold} streamed "
                      f"tokens (mid-decode)", flush=True)
                svc.router.kill(name)

        svc.router.token_observer = trigger

    cli = ServingClient(host, port)
    cli.metrics = svc.metrics     # client retries land in the ledger
    rng = np.random.default_rng(args.seed)
    rids, prompt_lens = [], {}
    for i in range(args.requests):
        prompt = rng.integers(0, cfg.vocab_size,
                              size=rng.integers(4, 12)).astype(np.int32)
        max_new = args.max_new
        budget = len(prompt) + max_new
        if len(prompt) >= args.max_len:
            print(f"[serve] REJECT req {i}: prompt length {len(prompt)} "
                  f">= max_len {args.max_len}")
            continue
        if budget > args.max_len:
            if args.strict_len:
                print(f"[serve] REJECT req {i}: over budget (--strict-len)")
                continue
            max_new = args.max_len - len(prompt)
        try:
            rid = cli.submit([int(t) for t in prompt],
                             max_new_tokens=max_new,
                             deadline_s=args.deadline,
                             max_queue_wait_s=args.max_queue_wait)
            rids.append(rid)
            prompt_lens[rid] = len(prompt)
        except RequestRejected as e:
            print(f"[serve] REJECT req {i}: {e}")
        except FrontendUnavailable as e:
            print(f"[serve] SHED req {i}: {e}")

    results = {}
    for rid in rids:
        try:
            results[rid] = cli.wait(rid, timeout=600.0)
        except TimeoutError as e:
            results[rid] = ("failed", [])
            print(f"[serve] TIMEOUT waiting on req {rid}: {e}")
    for rid in sorted(results):
        status, tokens = results[rid]
        print(f"[serve] req {rid}: prompt_len={prompt_lens.get(rid)} "
              f"generated={tokens} status={status}")

    svc.begin_drain()
    svc.shutdown()
    svc.check_shutdown_invariants()
    m = svc.metrics.summary()
    print(f"[serve] service: {args.replicas} replicas, "
          f"failovers={int(m['failovers'])}, "
          f"restarts={int(m['replica_restarts'])}, "
          f"kills={int(m['replica_kills'])}, "
          f"retries={int(m['retries'])}, "
          f"sheds={int(m['frontend_sheds'])}, "
          f"duplicate_terminals={int(m['duplicate_terminals'])}, "
          f"wal_replayed={int(m['wal_replayed'])}, "
          f"heartbeat age max {m['heartbeat_age_max']:.2f}s, "
          f"peak pending {int(m['peak_pending'])}")
    counts = {k[len("status_"):]: int(v) for k, v in m.items()
              if k.startswith("status_")}
    statuses = " ".join(f"{k}={v}" for k, v in sorted(counts.items()))
    print(f"[serve] statuses: {statuses or 'none'}")


def serving_config(arch: str, full: bool = False):
    """The config ``serve`` runs: published widths with ``full``, else the
    smoke variant; frontends are stripped (requests carry token ids)."""
    cfg = get_config(arch)
    if not full:
        cfg = smoke_variant(cfg)
    if cfg.is_encdec or cfg.frontend != "none":
        import dataclasses
        cfg = dataclasses.replace(cfg, frontend="none", frontend_len=0)
    return cfg


def load_params(arch: str, *, full: bool = False, train_steps: int = 10,
                seed: int = 0, bits: int = 0, gamma: float = 0.05):
    """(cfg, params) to serve. ``train_steps > 0`` trains the smoke model
    briefly; 0 draws seeded weights with ``init_model`` and never builds
    optimizer state. ``bits`` > 0 quantizes with ICQuant (RTN)."""
    import jax

    from repro.models import init_model

    cfg = serving_config(arch, full)
    if train_steps:
        params, _ = train(arch, steps=train_steps, batch=4, seq=64,
                          ckpt_dir=os.path.join(tempfile.gettempdir(),
                                                "repro_serve_ckpt"))
    else:
        params = init_model(jax.random.PRNGKey(seed), cfg)
    if bits:
        params, acct = quantize_tree(params, bits, gamma=gamma)
        print(f"[serve] quantized to {acct['mean_bits']:.2f} bits/weight")
    return cfg, params


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--bits", type=int, default=0,
                    help="ICQuant bits (0 = serve FP weights)")
    ap.add_argument("--gamma", type=float, default=0.05)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--max-len", type=int, default=64,
                    help="KV-cache length: every request must satisfy "
                         "len(prompt) + max_new_tokens <= max_len "
                         "(over-budget requests are truncated with a "
                         "warning, or rejected with --strict-len)")
    ap.add_argument("--strict-len", action="store_true",
                    help="reject over-budget requests instead of "
                         "truncating their token budget")
    ap.add_argument("--mode", default="auto",
                    choices=["auto", "continuous", "wave"],
                    help="'continuous' = slot scheduler with lane "
                         "recycling (default where supported), 'wave' = "
                         "legacy wave-synchronous static batching")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="continuous-mode chunked prefill: drain admitted "
                         "prompts S tokens per launch through a second "
                         "jitted chunk program (routes prompt matmuls "
                         "through the large-M dequant+MXU kernel arm, "
                         "cutting TTFT for long prompts). 1 = walk prompts "
                         "token-by-token inside the decode program (the "
                         "legacy behavior, bit-for-bit); default follows "
                         "ICQ_PREFILL_CHUNK (1). Greedy output is "
                         "token-identical either way")
    ap.add_argument("--kv-layout", default=None,
                    choices=["contiguous", "paged"],
                    help="KV-cache layout (continuous mode): 'contiguous' "
                         "charges batch*max_len rows up front; 'paged' "
                         "serves from a block pool with per-lane page "
                         "tables, decoupling cache HBM from max_len "
                         "(allocator-aware admission + preempt-and-requeue "
                         "under pressure; greedy output is token-identical "
                         "either way). Default follows ICQ_KV_LAYOUT "
                         "(contiguous)")
    ap.add_argument("--kv-block-size", default=None,
                    help="paged KV: cache rows per block, or 'auto' to "
                         "use the block-size sweep winner from the shared "
                         "autotune cache (default ICQ_KV_BLOCK_SIZE / 16)")
    ap.add_argument("--kv-blocks", type=int, default=None,
                    help="paged KV: physical blocks in the pool (default "
                         "batch * ceil(max_len / block_size) = contiguous "
                         "capacity; shrink to oversubscribe and trade "
                         "preemptions for HBM)")
    ap.add_argument("--max-queue", type=int, default=None,
                    help="bounded submit queue: refuse admission beyond "
                         "this many waiting requests (default "
                         "ICQ_MAX_QUEUE / unbounded)")
    ap.add_argument("--shed-policy", default=None,
                    choices=["reject", "shed-oldest"],
                    help="what a full queue sheds: 'reject' the new "
                         "request or 'shed-oldest' waiting one (default "
                         "ICQ_SHED_POLICY / reject)")
    ap.add_argument("--deadline", type=float, default=None,
                    help="per-request deadline in seconds from arrival; "
                         "lanes past it finish with status 'timeout' "
                         "(default: none)")
    ap.add_argument("--max-queue-wait", type=float, default=None,
                    help="per-request bound on queue wait in seconds; "
                         "requests not admitted in time finish with "
                         "status 'expired' (default: none)")
    ap.add_argument("--fault-plan", default=None,
                    help="deterministic fault injection, e.g. "
                         "'3:nan,6:raise' = launch 3 produces NaN "
                         "logits, launch 6 raises (default "
                         "ICQ_FAULT_PLAN)")
    ap.add_argument("--fault-rate", type=float, default=None,
                    help="seeded random fault injection probability per "
                         "launch (default ICQ_FAULT_RATE / 0)")
    ap.add_argument("--fault-seed", type=int, default=None,
                    help="PRNG seed for --fault-rate draws (default "
                         "ICQ_FAULT_SEED / 0)")
    ap.add_argument("--degrade-steps", type=int, default=None,
                    help="after a recovered fault, pin this many launches "
                         "to the bitwise-exact XLA arm before returning "
                         "to the fast path (default ICQ_DEGRADE_STEPS "
                         "/ 8)")
    ap.add_argument("--prefix-cache", action="store_true", default=None,
                    help="share identical prompt prefixes copy-on-write "
                         "across requests and retain finished chains for "
                         "reuse (paged KV only; default ICQ_PREFIX_CACHE "
                         "/ off). Implies --kv-layout paged when the "
                         "layout is unset")
    ap.add_argument("--sessions", type=int, default=0,
                    help="run a multi-turn chat workload instead of the "
                         "independent-request one: this many concurrent "
                         "sessions sharing one system prompt, each turn "
                         "extending its own history (requires/implies "
                         "--prefix-cache; turn 2+ prompts warm-start from "
                         "the previous turn's retained blocks)")
    ap.add_argument("--turns", type=int, default=3,
                    help="turns per session for --sessions (default 3)")
    ap.add_argument("--session-ttl", type=float, default=None,
                    help="idle seconds before a session's retained blocks "
                         "are dropped (default ICQ_SESSION_TTL / 300)")
    ap.add_argument("--shared-prefix", type=int, default=12,
                    help="shared system-prompt length in tokens for the "
                         "--sessions workload (default 12)")
    ap.add_argument("--replicas", type=int, default=0,
                    help="serve through the resilient service layer: this "
                         "many supervised engine replicas behind the TCP "
                         "frontend + router (WAL-journaled, failover on "
                         "replica death), driven by the retrying client. "
                         "0 (default) = the in-process engine path, "
                         "bit-for-bit the pre-service behavior")
    ap.add_argument("--wal", default=None,
                    help="request-journal path for --replicas (default "
                         "ICQ_WAL_PATH / no journal); an existing journal "
                         "is recovered and its unfinished requests "
                         "replayed before new traffic")
    ap.add_argument("--max-pending", type=int, default=None,
                    help="frontend backpressure bound for --replicas: shed "
                         "submits (retryable) beyond this many pending "
                         "requests (default: unbounded)")
    ap.add_argument("--kill-replica", default=None, metavar="I:N",
                    help="chaos drill for --replicas: hard-kill replica I "
                         "once N tokens have streamed service-wide "
                         "(mid-decode); supervision must fail its "
                         "requests over and restart it")
    ap.add_argument("--spec-decode", action="store_true", default=None,
                    help="speculative decoding: draft-and-verify pure-"
                         "decode iterations (greedy lanes only; output "
                         "token-identical to plain decode, only launch "
                         "count changes). Default ICQ_SPEC_DECODE / off")
    ap.add_argument("--spec-k", type=int, default=None,
                    help="draft tokens proposed per lane per speculative "
                         "iteration; the verify launch scores k+1 "
                         "positions per lane (default ICQ_SPEC_K / 4)")
    ap.add_argument("--draft", default=None,
                    choices=["ngram", "self2bit", "tiny", "reject"],
                    help="drafter for --spec-decode: 'ngram' host-side "
                         "prompt lookup (zero extra launches), 'self2bit' "
                         "the serving weights re-quantized at 2 bits, "
                         "'tiny' a dense 1-layer shrunk config, 'reject' "
                         "an adversarial always-wrong drafter (rollback "
                         "stress). Default ICQ_SPEC_DRAFT / ngram")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="0 = greedy; > 0 samples (continuous mode)")
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--top-p", type=float, default=1.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--train-steps", type=int, default=10,
                    help="train the smoke model this many steps before "
                         "serving; 0 serves seeded random weights "
                         "(init_model(PRNGKey(seed))) and builds no "
                         "optimizer state")
    ap.add_argument("--full", action="store_true",
                    help="serve the published (non-smoke) config; needs "
                         "--train-steps 0 (training at full width would "
                         "not fit one chip)")
    ap.add_argument("--weight-cache", default="prepared",
                    choices=["prepared", "dense", "none"],
                    help="load-time ICQ weight conversion: 'prepared' = "
                         "kernel dispatch layout, 'dense' = dequant-once "
                         "cache, 'none' = reference in-graph decode")
    ap.add_argument("--runtime-fmt", default=None, choices=["v1", "v2"],
                    help="prepared runtime format: 'v2' checkpointed gap "
                         "stream (~0.3-0.45 b/w outlier overhead, default) "
                         "or 'v1' dense selector bitmap (~1 b/w); default "
                         "follows ICQ_RUNTIME_FMT / platform policy")
    args = ap.parse_args()
    if args.kv_block_size is not None and args.kv_block_size != "auto":
        args.kv_block_size = int(args.kv_block_size)
    if args.sessions and args.prefix_cache is None:
        print("[serve] --sessions implies --prefix-cache; enabling it")
        args.prefix_cache = True
    if args.prefix_cache and args.kv_layout is None:
        args.kv_layout = "paged"

    if args.full and args.train_steps:
        ap.error("--full serves seeded weights: pass --train-steps 0")
    use_compile_cache()
    cfg, params = load_params(args.arch, full=args.full,
                              train_steps=args.train_steps, seed=args.seed,
                              bits=args.bits, gamma=args.gamma)

    sampling = SamplingParams(temperature=args.temperature,
                              top_k=args.top_k, top_p=args.top_p)
    if args.replicas:
        return _serve_replicas(args, params, cfg, sampling)
    faults = None
    if args.fault_plan is not None or args.fault_rate is not None:
        faults = FaultInjector(
            parse_fault_plan(args.fault_plan) if args.fault_plan else None,
            seed=args.fault_seed if args.fault_seed is not None else 0,
            rate=args.fault_rate if args.fault_rate is not None else 0.0)
    engine = GenerationEngine(params, cfg, batch_size=args.batch,
                              max_len=args.max_len,
                              weight_cache=args.weight_cache,
                              runtime_fmt=args.runtime_fmt,
                              mode=args.mode, sampling=sampling,
                              seed=args.seed,
                              prefill_chunk=args.prefill_chunk,
                              kv_layout=args.kv_layout,
                              kv_block_size=args.kv_block_size,
                              kv_blocks=args.kv_blocks,
                              max_queue=args.max_queue,
                              shed_policy=args.shed_policy,
                              faults=faults,
                              degrade_steps=args.degrade_steps,
                              prefix_cache=args.prefix_cache,
                              session_ttl=args.session_ttl,
                              spec_decode=args.spec_decode,
                              spec_k=args.spec_k,
                              spec_draft=args.draft)
    kv_desc = engine.kv_layout
    if engine.kv_layout == "paged":
        kv_desc += (f": {engine.kv_blocks} blocks x "
                    f"{engine.kv_block_size} rows")
        if engine.prefix_cache:
            kv_desc += ", prefix-cache on"
    spec_desc = (f", spec_decode=k{engine.spec_k}/{engine.spec_draft}"
                 if engine.spec_decode else "")
    print(f"[serve] engine mode: {engine.mode} (max_len={args.max_len}, "
          f"prefill_chunk={engine.prefill_chunk}, "
          f"fused_step={engine.fused_step}, kv={kv_desc}{spec_desc})")
    _install_engine_signals(engine)

    rng = np.random.default_rng(args.seed)
    if args.sessions:
        # Multi-turn chat workload: every session shares one system
        # prompt; each turn appends fresh user tokens to the session's
        # full history (prior prompt + generated reply). Turn 1 shares
        # the system prompt across sessions through the hash cache;
        # turn 2+ warm-starts from the session's retained chain, so
        # only the delta past the previous turn is prefilled.
        system = rng.integers(0, cfg.vocab_size,
                              size=args.shared_prefix).astype(np.int32)
        history = {sid: system.copy() for sid in range(args.sessions)}
        rid = 0
        for turn in range(args.turns):
            turn_rids = {}
            for sid in range(args.sessions):
                user = rng.integers(
                    0, cfg.vocab_size,
                    size=int(rng.integers(4, 9))).astype(np.int32)
                prompt = np.concatenate([history[sid], user])
                max_new = min(args.max_new, args.max_len - len(prompt))
                if len(prompt) >= args.max_len or max_new < 1:
                    print(f"[serve] session {sid} turn {turn}: history "
                          f"{len(prompt)} tokens overflows max_len "
                          f"{args.max_len}; skipping turn")
                    continue
                req = Request(rid, prompt, max_new_tokens=max_new,
                              deadline_s=args.deadline,
                              max_queue_wait_s=args.max_queue_wait,
                              arrival_time=engine.now())
                try:
                    if engine.submit(req, session=f"s{sid}"):
                        turn_rids[rid] = sid
                    else:
                        print(f"[serve] SHED session {sid} turn {turn}")
                except ValueError as e:
                    print(f"[serve] REJECT session {sid} turn {turn}: {e}")
                rid += 1
            done = engine.run()
            for r_id, sid in sorted(turn_rids.items()):
                r = done[r_id]
                print(f"[serve] session {sid} turn {turn}: "
                      f"prompt_len={len(r.prompt)} "
                      f"generated={r.generated} status={r.status}")
                if r.status == "ok":
                    history[sid] = np.concatenate(
                        [r.prompt, np.asarray(r.generated, np.int32)])
    else:
        for rid in range(args.requests):
            prompt = rng.integers(0, cfg.vocab_size,
                                  size=rng.integers(4, 12))
            prompt = prompt.astype(np.int32)
            max_new = args.max_new
            budget = len(prompt) + max_new
            if len(prompt) >= args.max_len:
                print(f"[serve] REJECT req {rid}: prompt length "
                      f"{len(prompt)} >= max_len {args.max_len}")
                continue
            if budget > args.max_len:
                if args.strict_len:
                    print(f"[serve] REJECT req {rid}: prompt "
                          f"{len(prompt)} + max_new {max_new} = {budget} "
                          f"> max_len {args.max_len} (--strict-len)")
                    continue
                max_new = args.max_len - len(prompt)
                print(f"[serve] WARN req {rid}: prompt {len(prompt)} + "
                      f"max_new {args.max_new} exceeds max_len "
                      f"{args.max_len}; truncating budget to {max_new} "
                      f"new tokens")
            try:
                accepted = engine.submit(
                    Request(rid, prompt, max_new_tokens=max_new,
                            deadline_s=args.deadline,
                            max_queue_wait_s=args.max_queue_wait))
                if not accepted:
                    print(f"[serve] SHED req {rid}: queue full "
                          f"(max_queue={engine.max_queue}, "
                          f"policy={engine.shed_policy})")
            except ValueError as e:
                # e.g. a paged pool too small to ever serve this request:
                # mirror the max_len policy above — reject, don't crash
                print(f"[serve] REJECT req {rid}: {e}")

        done = engine.run()
        for rid in sorted(done):
            r = done[rid]
            print(f"[serve] req {rid}: prompt_len={len(r.prompt)} "
                  f"generated={r.generated} status={r.status}")
    s = engine.metrics.summary()
    print(f"[serve] {int(s['completed'])}/{int(s['requests'])} requests, "
          f"{int(s['generated_tokens'])} tokens in {s['wall_s']:.2f}s "
          f"({s['tokens_per_s']:.1f} tok/s, mean occupancy "
          f"{s['mean_occupancy']:.2f}/{args.batch}, "
          f"ttft p50 {s['ttft_p50']:.3f}s, prompt split "
          f"{int(s['prefill_tokens'])} chunked / "
          f"{int(s['prompt_decode_tokens'])} walked)")
    print(f"[serve] launches: {int(s['launches'])} "
          f"({int(s['prefill_steps'])} chunk / "
          f"{int(s['decode_steps'])} decode / "
          f"{int(s['fused_steps'])} fused / "
          f"{int(s['verify_steps'])} verify / "
          f"{int(s['draft_launches'])} draft)")
    if s["fused_rows"]:
        print(f"[serve] fused rows: {int(s['fused_rows_live'])} live of "
              f"{int(s['fused_rows'])} computed")
    if engine.spec_decode:
        mal = s["mean_accept_len"]
        hist = " ".join(
            f"{a}:{n}" for a, n in
            sorted(engine.metrics.accept_hist.items()))
        print(f"[serve] speculative: spec_proposed="
              f"{int(s['spec_proposed'])} spec_accepted="
              f"{int(s['spec_accepted'])} mean_accept_len="
              f"{mal if mal != mal else round(mal, 2)} "
              f"(accept-len hist {hist or 'none'}, "
              f"{int(s['spec_fallbacks'])} verify fallbacks, "
              f"{int(s['spec_draft_errors'])} draft errors)")
    if s["paged_attn_window_fallbacks"]:
        print(f"[serve] paged-attn window fallbacks: "
              f"{int(s['paged_attn_window_fallbacks'])} decode launches "
              f"on the XLA gather arm (sliding window < page-table span)")
    if engine.kv_layout == "paged":
        print(f"[serve] paged KV: cache {int(s['cache_bytes'])} bytes "
              f"({int(s['kv_blocks'])} x {int(s['kv_block_size'])} rows), "
              f"{int(s['preemptions'])} preemptions, block utilization "
              f"{s['mean_block_utilization']:.2f} mean / "
              f"{int(s['peak_blocks_in_use'])} peak blocks")
    if engine.kv_layout == "paged" and engine.prefix_cache:
        rate = s["prefix_hit_rate"]
        rate_str = f"{rate:.3f}" if rate == rate else "n/a"
        print(f"[serve] prefix cache: {int(s['prefix_hits'])}/"
              f"{int(s['prefix_lookups'])} hits (hit rate {rate_str}), "
              f"{int(s['prefix_tokens_skipped'])} prefill tokens "
              f"skipped, {int(s['cow_forks'])} cow forks, "
              f"{int(s['prefix_inserts'])} chain inserts, "
              f"{int(s['prefix_evictions'])} evictions")
        print(f"[serve] sessions: {int(s['session_hits'])} warm hits, "
              f"{int(s['sessions_active'])} active at exit, "
              f"{int(s['session_expiries'])} expiries, "
              f"{int(s['session_evictions'])} evictions, shared blocks "
              f"mean {s['mean_shared_blocks']:.1f} / peak "
              f"{s['peak_shared_blocks']:.0f}")
    counts = engine.metrics.status_counts()
    statuses = " ".join(f"{k}={v}" for k, v in sorted(counts.items()))
    print(f"[serve] statuses: {statuses or 'none'}")
    if s["faults"] or s["degraded_steps"] or s["replays"]:
        by_kind = " ".join(f"{k}={v}" for k, v in
                           sorted(engine.metrics.faults.items()))
        print(f"[serve] faults: {int(s['faults'])} ({by_kind}), "
              f"{int(s['degraded_steps'])} degraded steps, "
              f"{int(s['replays'])} replays")
    print(f"[serve] watchdog: step time p50 {s['step_time_p50']:.4f}s / "
          f"p95 {s['step_time_p95']:.4f}s, "
          f"{int(s['stalled_steps'])} stalled steps")


if __name__ == "__main__":
    main()
