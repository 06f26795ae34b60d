"""Serving benchmarks for the async continuous-batching estimation
service (DESIGN.md §Serving). Two parts:

1. **Drain race** (real execution): one ragged multi-stream workload
   through the synchronous `BatchedEstimationService` and the
   `AsyncBatchedEstimationService`, warm-cache timed. Async dispatch
   overlaps host-side batch formation with device compute, so async must
   win windows/sec — at exactly equal results (the per-window warm-start
   reference chain is recomputed and compared).

2. **Open-loop Poisson load generator** (virtual time): real per-(length
   class, batch class) service times are calibrated once, then a
   discrete-event simulation drives the *same* scheduler state machine
   (`FakeClock` + `SimExecutor`, no device work) under Poisson arrivals
   across thousands of simulated streams. Reports p50/p99 latency,
   windows/sec, shed rate, and padding overhead per bucket policy, for
   the async service and a sync FIFO-drain baseline.

Both parts run once per serving workload plugin: the CMAX event-window
workload (top-level keys, back-compat with older baselines) and the LM
chunked-decode workload (`repro.serving.LMDecodeWorkload` on the smoke
transformer, under the `"lm"` key — its drain race gates on EXACT token
equality against the sequential unbatched chain, since int argmax
predictions admit no tolerance).

Scale knobs (environment):
  SERVING_BENCH_WORKLOADS comma list of workload arms to run
                          (default "cmax,lm")
  SERVING_BENCH_STREAMS   simulated streams        (default 1000; CI smoke.
                          Raise to 100000/1000000 locally — the DES is
                          pure Python over requests, no device work.)
  SERVING_BENCH_REQUESTS  total simulated windows  (default 6 per stream,
                          capped at 20000 in smoke; uncapped when set)
  SERVING_BENCH_UTIL      offered load as a fraction of calibrated
                          full-batch capacity (default 0.85)
  BENCH_SERVING_OUT       where to write the JSON baseline
                          (default <repo>/BENCH_serving.json)
"""
from __future__ import annotations

import json
import math
import os
import time
import types
from collections import deque
from typing import Callable, Dict, List, Tuple

import numpy as np
import jax.numpy as jnp

from .common import emit, time_call
from repro.core import CmaxConfig, estimate_batch
from repro.data import events as ev_data
from repro.data import lm as lm_data
from repro.launch.serve import (AsyncBatchedEstimationService,
                                BatchedEstimationService, FakeClock)
from repro.serving import LMDecodeWorkload
from repro.telemetry import Telemetry

N_STREAMS = 8            # drain race: real streams
N_WINDOWS = 4            # drain race: windows per stream
MIN_EVENTS, MAX_EVENTS = 1200, 4096
MAX_BATCH = 4
DEADLINE_BATCHES = 3.0   # SLO: this many full-batch service times
HI_PRIO_FRAC = 0.1       # fraction of simulated windows in the hi class

LM_ARCH = "llama3.2-1b"  # smoke config (repro.configs.get_smoke_config)
LM_STREAMS = 4           # LM drain race: real streams
LM_CHUNKS = 2            # chunks per stream
LM_MIN_TOK, LM_MAX_TOK = 6, 24
LM_MAX_LEN = 64          # carried-cache capacity >= LM_CHUNKS * LM_MAX_TOK


def _repo_root() -> str:
    return os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


#: serialized spans + decision records accumulated across the benchmark,
#: written as one JSONL file when BENCH_SERVING_TRACE_OUT (or run.py's
#: --trace-out) names a path
_TRACE_SINK: List[dict] = []


# ---------------------------------------------------------------------------
# part 1: real-execution drain race (sync vs async) + exact equivalence
# ---------------------------------------------------------------------------


def _workload(cam) -> Dict[str, Tuple[List, np.ndarray]]:
    """S ragged streams with ground truth: {stream: ([windows], omega_true)}."""
    out = {}
    for s in range(N_STREAMS):
        spec = ev_data.SequenceSpec(
            name=f"s{s}", n_windows=N_WINDOWS, events_per_window=MAX_EVENTS,
            seed=300 + s, camera=cam, omega_scale=3.0, window_dt=0.02)
        wins, om_true, _ = ev_data.make_sequence(spec)
        lens = ev_data.ragged_lengths(N_WINDOWS, MIN_EVENTS, MAX_EVENTS,
                                      seed=s)
        out[f"s{s}"] = (ev_data.ragged_from_sequence(wins, lens),
                        np.asarray(om_true))
    return out


def _submit_all(svc, workload) -> int:
    n = 0
    for sid, (ragged, _) in workload.items():
        for w in ragged:
            svc.submit(sid, w)
            n += 1
    return n


def _timed_pass(svc, workload) -> Tuple[float, list]:
    """One warm drain of the full workload; returns (windows/sec, resp)."""
    svc._warm.clear()
    n = _submit_all(svc, workload)
    t0 = time.perf_counter()
    responses = svc.drain()
    rate = n / (time.perf_counter() - t0)
    assert len(responses) == n
    return rate, responses


def _reference_chain(cfg, workload, policy) -> Dict[Tuple[str, int],
                                                    np.ndarray]:
    """Sequential reference: one window at a time, in stream order, warm-
    start chained, through the same jitted batch pipeline at batch 1.
    Any service variant must reproduce this bit-exactly — batching and
    scheduling must never change results. (On the engines that map over
    slots a slot also equals the unbatched `estimate_window` bit for
    bit, tests/test_batching.py.)"""
    ref = {}
    for sid, (ragged, _) in workload.items():
        om = np.zeros((1, 3), np.float32)
        for k, w in enumerate(ragged):
            batch = ev_data.batch_windows([w], policy.bucket_of(w.n))
            r = estimate_batch(batch, jnp.asarray(om), cfg)
            om = np.asarray(r.omega)
            ref[(sid, k)] = om[0]
    return ref


def _drain_race(cfg, workload, policy) -> dict:
    # dispatch depth: deeper in-flight windows only pay off when batches
    # can actually compute concurrently; on a single-core host two
    # in-flight batches just contend, so keep one computing and overlap
    # dispatch/harvest with it (the donated-buffer refill still applies).
    # sched_getaffinity sees container cpusets that cpu_count ignores.
    try:
        cores = len(os.sched_getaffinity(0))
    except AttributeError:
        cores = os.cpu_count() or 1
    depth = 2 if cores > 1 else 1
    # decision logging ON during the timed race: its cost is part of what
    # the telemetry-overhead CI gate bounds, and the log must reproduce
    # every response's per-stage iteration counts exactly
    tel = Telemetry(decisions=True)
    services = {
        "sync": BatchedEstimationService(cfg, policy=policy,
                                         max_batch=MAX_BATCH),
        "async": AsyncBatchedEstimationService(cfg, policy=policy,
                                               max_batch=MAX_BATCH,
                                               max_in_flight=depth,
                                               telemetry=tel),
    }
    for svc in services.values():   # cold pass compiles every shape class
        _submit_all(svc, workload)
        svc.drain()
    # interleave the timed reps so slow machine-load drift hits both
    # services equally; the median rejects the remaining spikes
    rates = {name: [] for name in services}
    last = {}
    for _ in range(3):
        for name, svc in services.items():
            rate, responses = _timed_pass(svc, workload)
            rates[name].append(rate)
            last[name] = responses
    wps_sync = float(np.median(rates["sync"]))
    wps_async = float(np.median(rates["async"]))
    resp_sync, resp_async = last["sync"], last["async"]

    ref = _reference_chain(cfg, workload, policy)
    worst = 0.0
    for responses in (resp_sync, resp_async):
        for r in responses:
            # warm-pass seqs continue past the cold pass: window index is
            # seq mod N_WINDOWS (the warm chain was reset between passes)
            dev = float(np.abs(
                r.omega - ref[(r.stream_id, r.seq % N_WINDOWS)]).max())
            worst = max(worst, dev)

    # the decision log must reproduce every async response's per-stage
    # iteration counts EXACTLY (the telemetry acceptance criterion)
    logged = tel.decisions.iters_by_request()
    iters_mismatch = sum(
        1 for r in resp_async
        if logged.get((r.stream_id, r.seq)) != tuple(r.iters))
    verdicts = tel.decisions.verdict_counts()

    out = dict(sync_windows_per_s=wps_sync, async_windows_per_s=wps_async,
               speedup=wps_async / wps_sync, max_abs_dev=worst,
               max_in_flight=depth,
               decision_records=len(tel.decisions.records),
               iters_match=iters_mismatch == 0, verdicts=verdicts)
    emit("serving_drain_race", 0.0,
         f"sync_wps={wps_sync:.2f};async_wps={wps_async:.2f};"
         f"speedup={out['speedup']:.3f}")
    emit("serving_equivalence", 0.0, f"max_abs_dev={worst:.2e}")
    emit("serving_decision_log", 0.0,
         f"records={out['decision_records']};"
         f"iters_mismatch={iters_mismatch}")
    assert worst < 1e-4, f"batched deviates from sequential ref by {worst}"
    assert iters_mismatch == 0, \
        f"decision log disagrees with {iters_mismatch} responses' iters"
    if os.environ.get("BENCH_SERVING_TRACE_OUT"):
        _TRACE_SINK.extend(tel.decisions.records)
    return out


# ---------------------------------------------------------------------------
# part 2: calibration + virtual-time Poisson load generator
# ---------------------------------------------------------------------------


def _rand_window(n: int, cam, seed: int = 0):
    rng = np.random.default_rng(seed + n)
    return ev_data.EventWindow(
        x=jnp.asarray(rng.integers(0, cam.width, n).astype(np.float32)),
        y=jnp.asarray(rng.integers(0, cam.height, n).astype(np.float32)),
        t=jnp.asarray(np.sort(rng.uniform(0, 0.02, n)).astype(np.float32)),
        p=jnp.asarray(rng.choice([-1.0, 1.0], n).astype(np.float32)),
        valid=jnp.asarray(np.ones(n, bool)))


def _calibrate(cfg, policies) -> Dict[Tuple[int, int], float]:
    """Measured service time (seconds) per (length class, batch class),
    at the class corners batch=1 and batch=MAX_BATCH. Executables are
    shared with the services (same module-level jit, same cfg), so this
    prices exactly what the scheduler dispatches."""
    classes = sorted({c for p in policies.values()
                      for c in p.classes(MIN_EVENTS, MAX_EVENTS)})
    cam = cfg.camera
    table: Dict[Tuple[int, int], float] = {}
    for n in classes:
        w = _rand_window(n, cam)
        for b in (1, MAX_BATCH):
            ev, _ = ev_data.fill_batch([w], n, b)
            us = time_call(
                lambda ev=ev, b=b: estimate_batch(ev, jnp.zeros((b, 3)), cfg),
                iters=3, warmup=1)
            table[(n, b)] = us / 1e6
    return table


def _svc_time_fn(table) -> Callable[[int, int], float]:
    """Interpolate the calibration corners linearly in batch size."""
    def t(bucket: int, batch: int) -> float:
        t1, tb = table[(bucket, 1)], table[(bucket, MAX_BATCH)]
        if batch >= MAX_BATCH:
            return tb
        return t1 + (tb - t1) * (batch - 1) / (MAX_BATCH - 1)
    return t


class SimExecutor:
    """Virtual-time executor: a single serial device with calibrated
    service times. `needs_data = False` tells the service to skip batch
    materialization, so the DES runs the full admission/refill/shed state
    machine with no array work at all — 10^6 requests are just Python."""

    needs_data = False

    def __init__(self, clock: FakeClock,
                 svc_time: Callable[[int, int], float],
                 null_result: Callable[[int, int], object] = None):
        self.clock = clock
        self.svc_time = svc_time
        # workload.null_result(bucket_n, batch_b): the placeholder the
        # plugin's harvest() can consume; default is the CMAX shape
        self._null = null_result or (
            lambda bucket_n, batch_b: types.SimpleNamespace(
                omega=np.zeros((batch_b, 3), np.float32), stages=()))
        self._done_at: Dict[int, float] = {}
        self._shape: Dict[int, Tuple[int, int]] = {}
        self._free = 0.0        # when the simulated device next idles
        self._next = 0
        self.busy_s = 0.0

    def submit(self, fn, ev_batch, om_batch, bucket_n: int, batch_b: int):
        h = self._next
        self._next += 1
        dt = self.svc_time(bucket_n, batch_b)
        start = max(self.clock.now(), self._free)
        self._free = start + dt
        self.busy_s += dt
        self._done_at[h] = self._free
        self._shape[h] = (bucket_n, batch_b)
        return h

    def done(self, handle) -> bool:
        return self.clock.now() >= self._done_at[handle]

    def wait(self, handle):
        self.clock.advance_to(self._done_at[handle])
        return self._null(*self._shape[handle])

    def next_completion(self) -> float:
        now = self.clock.now()
        ts = [t for t in self._done_at.values() if t > now]
        return min(ts) if ts else math.inf


def _trace(svc_time, policy, n_streams: int, n_requests: int, util: float,
           seed: int, n_min: int = MIN_EVENTS, n_max: int = MAX_EVENTS):
    """One open-loop Poisson arrival trace: the offered load is `util` x
    the calibrated full-batch capacity, so the trace shape is machine-
    independent even though absolute times are not."""
    rng = np.random.default_rng(seed)
    lens = rng.integers(n_min, n_max + 1, n_requests)
    per_window = float(np.mean([svc_time(policy.bucket_of(int(L)), MAX_BATCH)
                                / MAX_BATCH for L in lens[:512]]))
    rate = util / per_window                      # windows/s offered
    t_arr = np.cumsum(rng.exponential(1.0 / rate, n_requests))
    streams = rng.integers(0, n_streams, n_requests)
    hi = rng.random(n_requests) < HI_PRIO_FRAC
    deadline_s = DEADLINE_BATCHES * svc_time(policy.bucket_of(n_max),
                                             MAX_BATCH)
    return t_arr, lens, streams, hi, deadline_s


def _des_async(policy, svc_time, trace, n_streams: int,
               workload=None) -> dict:
    """Drive the real AsyncBatchedEstimationService in virtual time —
    with the default CMAX workload, or any plugin (its null_result feeds
    the plugin's own harvest, so the full admission/refill/shed/harvest
    path runs untouched)."""
    t_arr, lens, streams, hi, deadline_s = trace
    n = len(t_arr)
    clock = FakeClock()
    ex = SimExecutor(clock, svc_time,
                     null_result=workload.null_result if workload else None)
    # span tracing ON: the DES runs in virtual time, so the span phase
    # decomposition (queue_wait + assemble + execute) must telescope onto
    # each response's latency EXACTLY — asserted in _span_telemetry
    tel = Telemetry(spans=True)
    # dispatch depth 2 (the production default): deeper windows would
    # just move queue wait into un-sheddable device backlog — a request
    # already dispatched is never shed, so SLO control needs the queue
    if workload is not None:
        svc = AsyncBatchedEstimationService(
            workload=workload, max_batch=MAX_BATCH, clock=clock,
            executor=ex, max_in_flight=2, telemetry=tel)
    else:
        svc = AsyncBatchedEstimationService(
            CmaxConfig(), policy=policy, max_batch=MAX_BATCH, clock=clock,
            executor=ex, max_in_flight=2, telemetry=tel)
    responses: List = []
    i = 0
    while i < n or svc.in_flight() or svc.pending():
        t_next_done = ex.next_completion()
        if i < n and t_arr[i] <= t_next_done:
            clock.advance_to(float(t_arr[i]))
            svc.submit(f"s{streams[i]}",
                       types.SimpleNamespace(n=int(lens[i])),
                       priority=int(hi[i]),
                       deadline=clock.now() + deadline_s)
            i += 1
        elif t_next_done < math.inf:
            clock.advance_to(t_next_done)
        responses.extend(svc.poll())
    out = _metrics(responses, n_streams, span_end=clock.now(),
                   padded_slot_frac=svc.padded_slot_frac)
    out["telemetry"] = _span_telemetry(tel, svc, responses)
    return out


def _span_telemetry(tel: Telemetry, svc, responses) -> dict:
    """The BENCH_serving telemetry section for one instrumented run:
    queue-wait vs execute decomposition, compile-cache hit rate, and the
    shed breakdown — plus the exactness checks the spans must pass."""
    spans = [s.to_dict() for s in tel.tracer.spans]
    if os.environ.get("BENCH_SERVING_TRACE_OUT"):
        _TRACE_SINK.extend(spans)
    by_key = {(s["stream_id"], s["seq"]): s for s in spans}
    assert len(by_key) == len(spans) == len(responses)

    # every span's latency equals its response's latency bit-for-bit
    # (same clock reads), and the phases telescope onto it
    lat_mismatch = decomp_err = 0.0
    for r in responses:
        s = by_key[(r.stream_id, r.seq)]
        lat_mismatch = max(lat_mismatch, abs(s["latency_s"] - r.latency))
        decomp_err = max(decomp_err,
                         abs(sum(s["phases"].values()) - s["latency_s"]))
    assert lat_mismatch == 0.0, \
        f"span latency deviates from response latency by {lat_mismatch}"
    assert decomp_err <= 1e-9, \
        f"span phases do not telescope onto latency (err={decomp_err})"

    ok = [s for s in spans if s["status"] == "ok"]

    def _pct(key):
        v = np.asarray([s["phases"][key] for s in ok]) * 1e3
        return {"p50_ms": float(np.percentile(v, 50)),
                "p99_ms": float(np.percentile(v, 99)),
                "mean_ms": float(np.mean(v))}

    stats = svc.stats
    snap = tel.registry.snapshot()
    shed = snap.get("repro_serving_shed_total", {})
    return {
        "spans": len(spans),
        "queue_wait": _pct("queue_wait"),
        "assemble": _pct("assemble"),
        "execute": _pct("execute"),
        "decomposition_max_abs_err_s": float(decomp_err),
        "compile_cache_hit_rate":
            1.0 - stats["compiles"] / max(stats["batches"], 1),
        "shed": {"deadline": int(shed.get('reason="deadline"', 0)),
                 "budget": int(shed.get('reason="budget"', 0))},
    }


def _des_sync(policy, svc_time, trace, n_streams: int) -> dict:
    """Sync FIFO-drain baseline in the same virtual time: the service
    blocks through each batch, so arrivals are only admitted between
    steps; batch formation follows BatchedEstimationService._collect
    (leader's length class, one window per stream, FIFO). No deadlines —
    the sync API has none, every window is eventually computed."""
    t_arr, lens, streams, _, _ = trace
    n = len(t_arr)
    t = 0.0
    queue: deque = deque()
    i = 0
    latencies: List[float] = []
    event_slots = raw_events = 0
    while i < n or queue:
        if not queue and i < n:
            t = max(t, float(t_arr[i]))
        while i < n and t_arr[i] <= t:
            queue.append((float(t_arr[i]), int(lens[i]), int(streams[i])))
            i += 1
        if not queue:
            continue
        bucket = policy.bucket_of(queue[0][1])
        batch, seen, keep = [], set(), deque()
        while queue:
            req = queue.popleft()
            if req[2] not in seen and policy.bucket_of(req[1]) == bucket \
                    and len(batch) < MAX_BATCH:
                batch.append(req)
            else:
                keep.append(req)
            seen.add(req[2])
        queue = keep
        batch_b = 1 << max(0, (len(batch) - 1).bit_length())
        t += svc_time(bucket, batch_b)
        latencies.extend(t - ta for ta, _, _ in batch)
        event_slots += bucket * batch_b
        raw_events += sum(L for _, L, _ in batch)
    lat = np.asarray(latencies)
    span = t - float(t_arr[0])
    return dict(streams=n_streams, requests=n, served=n, shed_rate=0.0,
                p50_ms=float(np.percentile(lat, 50) * 1e3),
                p99_ms=float(np.percentile(lat, 99) * 1e3),
                windows_per_s=n / span,
                padded_slot_frac=(event_slots - raw_events)
                / max(event_slots, 1))


def _metrics(responses, n_streams: int, span_end: float,
             padded_slot_frac: float) -> dict:
    ok = [r for r in responses if r.status == "ok"]
    lat = np.asarray([r.latency for r in ok])
    span = span_end - min(r.t_submit for r in responses)
    return dict(streams=n_streams, requests=len(responses), served=len(ok),
                shed_rate=(len(responses) - len(ok)) / len(responses),
                p50_ms=float(np.percentile(lat, 50) * 1e3),
                p99_ms=float(np.percentile(lat, 99) * 1e3),
                windows_per_s=len(ok) / span,
                padded_slot_frac=padded_slot_frac)


# ---------------------------------------------------------------------------
# LM workload arm: same two parts through the LMDecodeWorkload plugin
# ---------------------------------------------------------------------------


def _lm_streams(cfg) -> Dict[str, List[lm_data.TokenChunk]]:
    dcfg = lm_data.LMDataConfig(vocab_size=cfg.vocab_size, seq_len=16,
                                global_batch=1, seed=7)
    return lm_data.token_streams(dcfg, LM_STREAMS, LM_CHUNKS,
                                 LM_MIN_TOK, LM_MAX_TOK, seed=7)


def _lm_submit_all(svc, streams) -> int:
    n = 0
    for sid, chunks in streams.items():
        for c in chunks:
            svc.submit(sid, c)
            n += 1
    return n


def _lm_reference(wl, streams) -> Dict[Tuple[str, int], np.ndarray]:
    """Sequential batch-1 chain through the plugin's own machinery —
    carried KV cache, one chunk at a time. Predictions are int argmax, so
    the service must match it EXACTLY, not within a tolerance."""
    ref = {}
    for sid, chunks in streams.items():
        state = wl.default_state()
        for k, c in enumerate(chunks):
            b = wl.bucket_of(c)
            data, sb, _ = wl.make_batch([c], [state], b, 1)
            res = wl.executable(b, 1, donate=False)(data, sb)
            out, state, _, _ = wl.harvest(res, False)(0)
            ref[(sid, k)] = np.asarray(out)
    return ref


def _lm_drain_race(wl, streams) -> dict:
    try:
        cores = len(os.sched_getaffinity(0))
    except AttributeError:
        cores = os.cpu_count() or 1
    depth = 2 if cores > 1 else 1
    services = {
        "sync": BatchedEstimationService(workload=wl, max_batch=MAX_BATCH),
        "async": AsyncBatchedEstimationService(workload=wl,
                                               max_batch=MAX_BATCH,
                                               max_in_flight=depth),
    }
    n_tok = sum(c.n for chunks in streams.values() for c in chunks)
    for svc in services.values():   # cold pass compiles every shape class
        _lm_submit_all(svc, streams)
        svc.drain()
    rates = {name: [] for name in services}
    last = {}
    for _ in range(3):              # interleaved reps, median (as cmax)
        for name, svc in services.items():
            svc._warm.clear()       # restart every carried-cache chain
            n = _lm_submit_all(svc, streams)
            t0 = time.perf_counter()
            responses = svc.drain()
            rates[name].append(n_tok / (time.perf_counter() - t0))
            last[name] = responses
            assert len(responses) == n
    tps_sync = float(np.median(rates["sync"]))
    tps_async = float(np.median(rates["async"]))

    ref = _lm_reference(wl, streams)
    mismatched = 0
    for responses in last.values():
        for r in responses:
            # warm-pass seqs continue past the cold pass: chunk index is
            # seq mod LM_CHUNKS (the cache chain was reset between passes)
            if not np.array_equal(np.asarray(r.omega),
                                  ref[(r.stream_id, r.seq % LM_CHUNKS)]):
                mismatched += 1

    out = dict(sync_tok_per_s=tps_sync, async_tok_per_s=tps_async,
               speedup=tps_async / tps_sync, mismatched_chunks=mismatched,
               exact=mismatched == 0, max_in_flight=depth)
    emit("serving_lm_drain_race", 0.0,
         f"sync_tps={tps_sync:.1f};async_tps={tps_async:.1f};"
         f"speedup={out['speedup']:.3f}")
    emit("serving_lm_equivalence", 0.0, f"mismatched_chunks={mismatched}")
    assert mismatched == 0, \
        f"{mismatched} served chunks deviate from the sequential LM chain"
    return out


def _lm_calibrate(wl, policies) -> Dict[Tuple[int, int], float]:
    """Measured decode time (seconds) per (token class, batch class) at
    the corners batch=1 and batch=MAX_BATCH, through the plugin's own
    executable — exactly what the scheduler dispatches."""
    classes = sorted({c for p in policies.values()
                      for c in p.classes(LM_MIN_TOK, LM_MAX_TOK)})
    rng = np.random.default_rng(11)
    table: Dict[Tuple[int, int], float] = {}
    for n in classes:
        c = lm_data.TokenChunk(
            rng.integers(0, wl.cfg.vocab_size, n).astype(np.int32))
        for b in (1, MAX_BATCH):
            data, sb, _ = wl.make_batch([c], [wl.default_state()], n, b)
            fn = wl.executable(n, b, donate=False)
            us = time_call(lambda fn=fn, data=data, sb=sb: fn(data, sb),
                           iters=3, warmup=1)
            table[(n, b)] = us / 1e6
    return table


def _lm_section(n_streams: int, n_requests: int, util: float) -> dict:
    """The full LM arm: drain race, calibration, Poisson DES."""
    from repro.configs import get_smoke_config

    cfg = get_smoke_config(LM_ARCH)
    policies = {
        "pow2": lm_data.chunk_policy(min_bucket=8, max_bucket=64),
        "single": ev_data.single_policy(32),
    }
    wl = LMDecodeWorkload(cfg, policy=policies["pow2"], max_len=LM_MAX_LEN)

    drain = _lm_drain_race(wl, _lm_streams(cfg))

    table = _lm_calibrate(wl, policies)
    for (bucket, batch), sec in sorted(table.items()):
        emit(f"serving_lm_calib_n{bucket}_b{batch}", sec * 1e6,
             f"ms_per_batch={sec * 1e3:.2f}")
    svc_time = _svc_time_fn(table)

    poisson = {}
    for pname, policy in policies.items():
        trace = _trace(svc_time, policy, n_streams, n_requests, util,
                       seed=43, n_min=LM_MIN_TOK, n_max=LM_MAX_TOK)
        # one plugin instance per policy (the service reads its policy
        # from the workload); params shared so nothing re-initializes
        des_wl = LMDecodeWorkload(cfg, params=wl.params, policy=policy,
                                  max_len=LM_MAX_LEN)
        res = {"async": _des_async(policy, svc_time, trace, n_streams,
                                   workload=des_wl),
               "sync": _des_sync(policy, svc_time, trace, n_streams)}
        poisson[pname] = res
        for mode, m in res.items():
            emit(f"serving_lm_poisson_{pname}_{mode}", m["p50_ms"] * 1e3,
                 f"p50_ms={m['p50_ms']:.2f};p99_ms={m['p99_ms']:.2f};"
                 f"windows_per_s={m['windows_per_s']:.1f};"
                 f"shed_rate={m['shed_rate']:.4f};"
                 f"padded_slot_frac={m['padded_slot_frac']:.3f}")

    return {
        "arch": LM_ARCH,
        "drain": drain,
        "calibration_ms": {f"n{b},b{k}": sec * 1e3
                           for (b, k), sec in sorted(table.items())},
        "poisson": poisson,
    }


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------


def run() -> dict:
    import jax

    wanted = {w.strip() for w in os.environ.get(
        "SERVING_BENCH_WORKLOADS", "cmax,lm").split(",") if w.strip()}
    n_streams = int(os.environ.get("SERVING_BENCH_STREAMS", "1000"))
    n_requests = int(os.environ.get(
        "SERVING_BENCH_REQUESTS", str(min(6 * n_streams, 20000))))
    util = float(os.environ.get("SERVING_BENCH_UTIL", "0.85"))

    results = {
        "meta": {"jax": jax.__version__,
                 "backend": jax.default_backend(),
                 "streams": n_streams, "requests": n_requests,
                 "util": util, "max_batch": MAX_BATCH,
                 "deadline_batches": DEADLINE_BATCHES,
                 "workloads": sorted(wanted)},
    }

    if "cmax" in wanted:
        cfg = CmaxConfig()
        policies = {
            "pow2": ev_data.pow2_policy(min_bucket=1024),
            "single": ev_data.single_policy(MAX_EVENTS),
        }
        drain = _drain_race(cfg, _workload(cfg.camera), policies["pow2"])

        table = _calibrate(cfg, policies)
        for (bucket, batch), sec in sorted(table.items()):
            emit(f"serving_calib_n{bucket}_b{batch}", sec * 1e6,
                 f"ms_per_batch={sec * 1e3:.2f}")
        svc_time = _svc_time_fn(table)

        poisson = {}
        for pname, policy in policies.items():
            trace = _trace(svc_time, policy, n_streams, n_requests, util,
                           seed=42)
            res = {"async": _des_async(policy, svc_time, trace, n_streams),
                   "sync": _des_sync(policy, svc_time, trace, n_streams)}
            poisson[pname] = res
            for mode, m in res.items():
                emit(f"serving_poisson_{pname}_{mode}", m["p50_ms"] * 1e3,
                     f"p50_ms={m['p50_ms']:.2f};p99_ms={m['p99_ms']:.2f};"
                     f"windows_per_s={m['windows_per_s']:.1f};"
                     f"shed_rate={m['shed_rate']:.4f};"
                     f"padded_slot_frac={m['padded_slot_frac']:.3f}")

        # cmax stays at the top level so older baselines remain diffable
        results["drain"] = drain
        results["calibration_ms"] = {f"n{b},b{k}": sec * 1e3
                                     for (b, k), sec in sorted(table.items())}
        results["poisson"] = poisson
        # the telemetry section: span decomposition from the pow2 async
        # DES (virtual time -> exact), decision-log summary from the real
        # drain race (real iteration counts)
        results["telemetry"] = dict(
            poisson["pow2"]["async"]["telemetry"],
            decisions={"records": drain["decision_records"],
                       "iters_match": drain["iters_match"],
                       "verdicts": drain["verdicts"]})
        t = results["telemetry"]
        emit("serving_telemetry", 0.0,
             f"queue_wait_p50_ms={t['queue_wait']['p50_ms']:.3f};"
             f"execute_p50_ms={t['execute']['p50_ms']:.3f};"
             f"compile_cache_hit_rate={t['compile_cache_hit_rate']:.3f};"
             f"decomp_err={t['decomposition_max_abs_err_s']:.1e}")

    if "lm" in wanted:
        results["lm"] = _lm_section(n_streams, n_requests, util)
    trace_path = os.environ.get("BENCH_SERVING_TRACE_OUT")
    if trace_path:
        from repro.telemetry import write_jsonl
        n_rec = write_jsonl(trace_path, _TRACE_SINK)
        emit("serving_trace_written", 0.0, f"{trace_path} ({n_rec} records)")
    out_path = os.environ.get(
        "BENCH_SERVING_OUT", os.path.join(_repo_root(), "BENCH_serving.json"))
    with open(out_path, "w") as f:
        json.dump(results, f, indent=2, sort_keys=True)
        f.write("\n")
    emit("serving_baseline_written", 0.0, out_path)
    return results
