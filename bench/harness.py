"""Drive the serving engine through one cell: set-up, window, check.

The measured entry is ``GenerationEngine.run`` in continuous mode with a
paged KV pool, fused chunked prefill and prepared ``v2`` weights, every
option passed from the cell's files. The harness uses the engine's
public hooks only: ``submit``; ``Request.on_token``, which stamps every
token on the host's monotonic clock; ``on_iteration``, called at the top
of every engine iteration, which refills the closed loop, opens and
closes the window, and records each launch (its program from the
engine's step counters, its lanes from the requests in flight, and its
change in each engine metric the architecture's ``STEP_COUNTERS``
names); and
``cancel``, for the requests still open when the window closes.

A run: weights (from the checkout's cache after the first run), engine,
one warm-up request through both step programs, a pre-roll until every
client has its first token plus ``preroll_s``, the window, then the
correctness check on a sample of what the window served.
"""
from __future__ import annotations

import contextlib
import gc
import importlib.util
import json
import os
import re
import sys
import time
from typing import Callable, Dict, List, Optional, Sequence

import numpy as np

from bench import check, tracing, traffic, weights, work
from bench import model as model_mod
from bench.manifest import BENCH_DIR, layer_metric_path

TRACE_DIR = os.path.join(BENCH_DIR, ".trace")


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


class NoChip(RuntimeError):
    pass


_COMPILES = [0]      # backend compilations in this process
_LISTENING = []


def _listen_compiles() -> None:
    import jax

    def count(name, *args, **kw):
        if name == "/jax/core/compile/backend_compile_duration":
            _COMPILES[0] += 1

    if not _LISTENING:
        jax.monitoring.register_event_duration_secs_listener(count)
        _LISTENING.append(count)


def load_reader(name: str, root: str) -> Callable:
    path = layer_metric_path(name, root)
    mod_name = "bench_layer_" + re.sub(r"\W", "_", name)
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class Window:
    """What a per-layer reader gets: the window's launches, counters and
    trace, with the model, its weight bytes and the chip's peaks."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


class Driver:
    """Closed-loop clients over one engine, as ``on_iteration`` sees it."""

    def __init__(self, eng, tr: traffic.Traffic, spec: traffic.Spec,
                 seconds: float, trace_dir: Optional[str],
                 compiles: Callable[[], int] = lambda: 0,
                 clock: Callable[[], float] = time.monotonic,
                 step_counters: Sequence[str] = ()):
        self.eng, self.tr, self.spec = eng, tr, spec
        self.step_counters = tuple(step_counters)
        self.seconds = seconds
        self.trace_dir = trace_dir
        self.clock = clock
        self.cur: Dict[int, object] = {}          # client -> Request
        self.recs: Dict[int, dict] = {}           # rid -> record
        self.consumed: Dict[int, int] = {}        # rid -> KV rows written
        self.phase = "preroll"
        self.t_ready: Optional[float] = None
        self.t_open = self.t_close = None
        self.open_snap = self.close_snap = None
        self.emitted = 0
        self.plan = None
        self.steps: List[work.Step] = []
        self.steps_ok = True
        self.compiles = compiles
        self.compiles_at_open = 0
        self.iter_at: List[float] = []            # window iterations' clock
        self._iter_span = None
        self._window_span = None

    # -- spans (only while tracing) ------------------------------------
    def _span(self, name: str):
        if self.phase == "window" and self.trace_dir is not None:
            import jax

            return jax.profiler.TraceAnnotation(name)
        return contextlib.nullcontext()

    # -- engine hooks ----------------------------------------------------
    def on_token(self, rid: int, tok: int) -> None:
        with self._span("bench.on_token"):
            self.recs[rid]["times"].append(self.clock())
            self.emitted += 1

    def submit(self, client: int) -> None:
        from repro.serving import Request

        r = self.tr.next(client)
        req = Request(r.idx, r.prompt, max_new_tokens=r.max_new,
                      on_token=self.on_token)
        with self._span("bench.submit"):
            t = self.clock()
            self.recs[r.idx] = dict(idx=r.idx, client=client, req=req,
                                    prompt=r.prompt, max_new=r.max_new,
                                    sent=t, times=[])
            self.consumed[r.idx] = 0
            self.cur[client] = req
            self.eng.submit(req)

    def _snap(self):
        m = self.eng.metrics
        return (m.fused_steps, m.decode_steps,
                m.prefill_tokens + m.prompt_decode_tokens, self.emitted)

    def _counts(self) -> List[np.ndarray]:
        """The engine metrics the architecture names for each launch."""
        return [np.array(getattr(self.eng.metrics, k))
                for k in self.step_counters]

    def _account(self) -> None:
        """Turn the launch the last iteration made into a ``Step``, and
        check the lanes the harness expected against the counters."""
        snap = self._snap()
        if self.plan is None:
            return
        prev, counts, lanes = self.plan
        self.plan = None
        d_fused, d_dec = snap[0] - prev[0], snap[1] - prev[1]
        if d_fused + d_dec == 0:
            if snap[2:] != prev[2:]:
                self.steps_ok = False
            return
        S = self.spec.prefill_chunk
        fused = any(P - c >= 2 for _, c, P in lanes)
        ns, prompt, out = [], 0, 0
        for rid, c, P in lanes:
            n = (min(S, P - c) if c < P else 1) if fused else 1
            if fused and c < P:
                prompt += n
            elif not fused and c + 1 < P:
                prompt += 1
            if c + n >= P:
                out += 1
            ns.append(n)
        seen = (d_fused, d_dec, snap[2] - prev[2], snap[3] - prev[3])
        if seen != (int(fused), int(not fused), prompt, out):
            self.steps_ok = False
        for (rid, c, _), n in zip(lanes, ns):
            self.consumed[rid] = c + n
        if self.phase == "window":
            self.steps.append(work.Step(
                "fused" if d_fused else "decode",
                [(c, n) for (_, c, _), n in zip(lanes, ns)],
                seen[2], seen[3],
                {k: (b - a).tolist() for k, a, b in zip(
                    self.step_counters, counts, self._counts())}))

    def on_iteration(self) -> None:
        if self._iter_span is not None:
            self._iter_span.__exit__(None, None, None)
            self._iter_span = None
        self._account()
        if self.phase == "closed":
            return
        now = self.clock()
        if self.phase == "window":
            self.iter_at.append(now)
        if self.phase == "window" and now - self.t_open >= self.seconds:
            self._close(now)
            return
        for client, req in list(self.cur.items()):
            if req.status is not None:
                self.submit(client)
        if self.phase == "preroll":
            if self.t_ready is None and all(
                    any(r["times"] for r in self.recs.values()
                        if r["client"] == c) for c in self.cur):
                self.t_ready = now
            if (self.t_ready is not None
                    and now - self.t_ready >= self.spec.preroll_s):
                self._open(now)
        self.plan = (self._snap(), self._counts(), [
            (req.rid, self.consumed[req.rid], len(req.prompt))
            for req in self.cur.values() if req.status is None])
        if self.phase == "window" and self.trace_dir is not None:
            self._iter_span = self._span("bench.iteration")
            self._iter_span.__enter__()

    def _open(self, now: float) -> None:
        if self.trace_dir is not None:
            import jax

            tracing.capture_start(self.trace_dir)
            self._window_span = jax.profiler.TraceAnnotation(
                tracing.WINDOW_SPAN)
            self._window_span.__enter__()
        self.phase = "window"
        self.t_open = self.clock() if self.trace_dir is not None else now
        self.open_snap = self._snap()
        self.iter_at = [self.t_open]
        self.compiles_at_open = self.compiles()

    def _close(self, now: float) -> None:
        self.t_close = now
        self.close_snap = self._snap()
        self.compiles_in_window = self.compiles() - self.compiles_at_open
        if self._window_span is not None:
            self._window_span.__exit__(None, None, None)
            tracing.capture_stop()
        self.phase = "closed"
        for req in self.cur.values():
            if req.status is None:
                self.recs[req.rid]["closed_open"] = True
                self.eng.cancel(req.rid)

    def drive(self) -> None:
        for client in range(self.spec.concurrency):
            self.submit(client)
        self.eng.on_iteration = self.on_iteration
        while self.phase != "closed":
            self.eng.run()
            if self.phase != "closed":
                self.on_iteration()
        while self.eng.has_work():
            self.eng.run()


def _pct(xs: List[float], q: float) -> float:
    return float(np.percentile(np.asarray(xs, np.float64), q)) if xs \
        else float("nan")


def _weight_bytes(m: model_mod.Model, params) -> work.Weights:
    return work.Weights(m.arch.layer_bytes(m, params),
                        weights.prepared_bytes(params["lm_head"]))


def run(config_file: str, traffic_file: str, e2e: Dict[str, str],
        per_layer: Dict[str, str], seed: int, seconds: float, trace: bool,
        chips: int = 1, require_tpu: bool = True,
        cache_dir: str = weights.CACHE_DIR, root: str = None,
        t_start: Optional[float] = None, control: bool = False) -> dict:
    """One run of a cell; returns the result line as a dict. Raises
    ``NoChip`` (before any work) where JAX finds no TPU or too few.
    ``control`` adds the control's verdict (``control``) on the same
    sample; the benchmark's own runs leave it off."""
    import jax

    from bench.manifest import ROOT

    root = root or ROOT
    t_start = time.monotonic() if t_start is None else t_start
    devices = jax.devices()
    if require_tpu and (devices[0].platform != "tpu"
                        or len(devices) < chips):
        raise NoChip(f"JAX runs on {len(devices)} {devices[0].platform} "
                     f"device(s); this cell needs {chips} TPU chip(s)")
    _listen_compiles()

    from repro.serving import GenerationEngine
    from repro.serving.sampling import GREEDY

    m = model_mod.load(config_file, root)
    spec = traffic.load(traffic_file)
    params, built = weights.load_or_build(m, config_file, cache_dir, log,
                                          root)
    if built is not None:
        log(f"first run: weights drawn {built['draw']:.1f} s, quantized "
            f"{built['quantize']:.1f} s, prepared {built['prepare']:.1f} s, "
            f"saved {built['save']:.1f} s")
    wb = _weight_bytes(m, params)
    B, L = spec.batch, spec.max_len
    eng = GenerationEngine(
        params, m.arch.program_config(m), batch_size=B, max_len=L,
        weight_cache="prepared", runtime_fmt=m.fmt, mode="continuous",
        sampling=GREEDY, seed=0, prefill_chunk=spec.prefill_chunk,
        kv_layout="paged", kv_block_size=spec.kv_block_size,
        kv_blocks=B * -(-L // spec.kv_block_size), max_queue=None,
        shed_policy="reject", faults=None, degrade_steps=8,
        fused_step=True, prefix_cache=False, session_ttl=0.0,
        spec_decode=False, spec_k=1, spec_draft="ngram")
    del params

    from repro.serving import Request

    t = time.monotonic()
    eng.submit(Request(-1, np.arange(1, 4, dtype=np.int32),
                       max_new_tokens=2))
    eng.run()
    ms = eng.metrics
    if not (ms.fused_steps >= 1 and ms.decode_steps >= 1):
        raise RuntimeError(
            f"warm-up did not run both step programs: fused "
            f"{ms.fused_steps}, decode {ms.decode_steps}, faults "
            f"{ms.faults}, degraded {ms.degraded_steps}, replays "
            f"{ms.replays}, status {eng.completed[-1].status}; device "
            f"memory {jax.devices()[0].memory_stats()}")
    log(f"warm-up (compile or cache load) {time.monotonic() - t:.1f} s; "
        f"peak HBM so far "
        f"{(jax.devices()[0].memory_stats() or {}).get('peak_bytes_in_use')}"
        f" bytes")

    tr = traffic.Traffic(spec, seed, m.vocab)
    tdir = None
    if trace:
        tdir = TRACE_DIR
        import shutil

        shutil.rmtree(tdir, ignore_errors=True)
    drv = Driver(eng, tr, spec, seconds, tdir,
                 compiles=lambda: _COMPILES[0],
                 step_counters=getattr(m.arch, "STEP_COUNTERS", ()))
    drv.drive()
    setup_s = drv.t_open - t_start
    T = drv.t_close - drv.t_open

    dev = jax.devices()[0]
    stats = dev.memory_stats() or {}
    device = dict(platform=dev.platform, kind=dev.device_kind,
                  count=jax.device_count(),
                  memory_peak_bytes=int(stats.get("peak_bytes_in_use", 0)))
    counters = dict(
        fused=drv.close_snap[0] - drv.open_snap[0],
        decode=drv.close_snap[1] - drv.open_snap[1],
        prompt_tokens=drv.close_snap[2] - drv.open_snap[2],
        out_tokens=drv.close_snap[3] - drv.open_snap[3])
    counters["launches"] = counters["fused"] + counters["decode"]
    for k in ("faults", "degraded_steps", "replays", "preemptions"):
        v = getattr(ms, k)
        counters[k] = sum(v.values()) if isinstance(v, dict) else v

    # -- end-to-end numbers -------------------------------------------------
    lo, hi = drv.t_open, drv.t_close
    n_out, itl, ttft = 0, [], []
    attempted = failed = 0
    for r in drv.recs.values():
        ts = r["times"]
        n_out += sum(lo <= t < hi for t in ts)
        itl += [b - a for a, b in zip(ts, ts[1:]) if lo <= a and b < hi]
        if lo <= r["sent"] < hi:
            attempted += 1
            first = ts[0] if ts and ts[0] < hi else hi
            ttft.append(first - r["sent"])
            st = r["req"].status
            if st not in ("ok", "cancelled", None):
                failed += 1
    e2e_vals = dict(
        output_tok_s=n_out / T,
        itl_p95_ms=_pct(itl, 95) * 1e3,
        ttft_p95_ms=_pct(ttft, 95) * 1e3,
        prefill_tok_s=counters["prompt_tokens"] / T,
        setup_s=setup_s)
    log(f"window {T:.3f} s: {n_out} output tokens, {len(itl)} gaps, "
        f"{attempted} requests sent, {len(ttft)} TTFT samples, "
        f"{counters['launches']} launches ({counters['fused']} fused, "
        f"{counters['decode']} decode), {counters['prompt_tokens']} prompt "
        f"tokens; compilations inside the window: {drv.compiles_in_window}"
        f"; faults {counters['faults']}, degraded {counters['degraded_steps']}"
        f", replays {counters['replays']}, preemptions "
        f"{counters['preemptions']}; step log "
        f"{'consistent' if drv.steps_ok else 'INCONSISTENT'}")
    gaps = np.diff(np.asarray(drv.iter_at, np.float64))
    if gaps.size:
        # a host or device stall shows as one iteration far above the median
        top = np.argsort(gaps)[::-1][:3]
        log(f"iterations {gaps.size}: median {np.median(gaps) * 1e3:.1f} ms; "
            "longest " + ", ".join(
                f"{gaps[i] * 1e3:.1f} ms at {drv.iter_at[i] - lo:.2f} s"
                for i in top))
    log(f"setup {setup_s:.2f} s; peak HBM {device['memory_peak_bytes']} "
        f"bytes")

    # -- per-layer numbers --------------------------------------------------
    breakdown = None
    if trace:
        tr_red = tracing.reduce(tdir)
        if tr_red is None:
            raise RuntimeError("the trace holds no device plane or no "
                               "window span")
        with open(os.path.join(tdir, "steps.json"), "w") as f:
            json.dump(dict(steps_ok=drv.steps_ok, counters=counters,
                           batch=B, weights=wb.__dict__,
                           steps=[s.__dict__ for s in drv.steps]), f)
        tracing.save(tr_red, os.path.join(tdir, "trace.json"))
        for k in ("icq_matmul_v2", "icq_dequant_v2", "paged_attention"):
            n, sec = tracing.kernel_s(tr_red, k)
            log(f"trace: {k} {n} events, {sec:.4f} s")
        busy = tracing.busy_s(tr_red)
        device.update(busy_s=busy, window_s=tr_red.window_s)
        breakdown = tracing.breakdown(tr_red)
        try:
            pk = work.peaks(dev.device_kind)
        except KeyError as e:          # the rooflines then read nothing
            log(f"ERROR {e}")
            pk = None
        win = Window(model=m, weights=wb, peaks=pk, batch=B,
                     seconds=T, steps=drv.steps, steps_ok=drv.steps_ok,
                     counters=counters, trace=tr_red)
        vals = {}
        for name in per_layer:
            try:
                v = load_reader(name, root)(win)
            except Exception as e:     # a reader fault leaves it out
                log(f"ERROR reader {name}: {type(e).__name__}: {e}")
                v = None
            if v is None:
                log(f"{name}: nothing to read")
            else:
                vals[name] = v
        metrics = {k: {"value": vals[k], "unit": u}
                   for k, u in per_layer.items() if k in vals}
    else:
        metrics = {k: {"value": e2e_vals[k], "unit": u}
                   for k, u in e2e.items()}

    # -- correctness, with the program's state freed ------------------------
    done = [dict(idx=r["idx"], prompt=r["prompt"],
                 served=np.asarray(r["req"].generated, np.int32),
                 finished=r["req"].status == "ok")
            for r in drv.recs.values()]
    short = sum(1 for r in drv.recs.values()
                if r["req"].status == "ok"
                and len(r["req"].generated) != r["max_new"])
    failed_all = sum(1 for r in drv.recs.values()
                     if r["req"].status not in ("ok", "cancelled", None))
    del eng, drv
    gc.collect()
    t = time.monotonic()
    picked = check.sample(done, spec.check_requests, seed)
    gap = ctl = None
    ref_times: Dict[str, float] = {}
    if picked:
        g, c = check.gaps(m, picked, pad_to=spec.max_len,
                          rows=spec.output["max"], lower=control,
                          times=ref_times)
        gap = float(g.max())
        ctl = float(c.max()) if control else None
    v = check.verdict(m, gap, short, failed_all)
    # the control in the program's place: its first choices at the same
    # positions, judged by the same comparison
    cv = check.verdict(m, ctl, short, failed_all) if control else None
    n_served = sum(len(r["served"]) for r in picked)
    log(f"check: {len(picked)} requests, {n_served} served tokens, "
        f"reference {time.monotonic() - t:.1f} s (quantize "
        f"{ref_times.get('quantize', 0):.1f} s, layers "
        f"{ref_times.get('layers', 0):.1f} s)")
    for k, (val, lim) in v["numbers"].items():
        log(f"check {k} {val} limit {lim}")
    result = dict(correct=v["correct"], attempted=attempted, failed=failed,
                  metrics=metrics, device=device)
    if control:
        result["control"] = dict(correct=cv["correct"], logit_gap=ctl)
        log(f"control: correct {cv['correct']}, logit_gap {ctl}")
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["check"] = {k: {"value": a, "limit": b}
                       for k, (a, b) in v["numbers"].items()}
    return result
