"""Per-window CMAX estimation pipeline: warp -> sort -> iterate -> promote.

This is the software twin of the CMAX-CAMEL engine + controller:

  for each stage s in {1/4, 1/2, 1}:                     (coarse-to-fine)
      sort_events(...)            # once per stage entry (Alg. 3)
      entry pass: (V_prev, grad)  # Alg. 1 line 2
      while_loop:                 # runtime-adaptive residence (Alg. 1)
          omega <- CG-PR(omega, grad)          # Update(omega, s)
          engine pass: IWE+dIWE -> blur -> (V, grad)     # one pass/iter
          g = (V - V_prev)/|V_prev|
          adaptive:  stay iff g >= tau_s  (else promote / terminate)
          fixed:     stay iff iter < fixed_iters[s]

Static shapes: each stage has its own (H_s, W_s) grid, so stages are chained
at the Python level (3 static stages) while the *residence within* a stage
is a data-dependent `lax.while_loop` — exactly the paper's split between
predetermined stage structure and runtime-adaptive residence.

`estimate_window` is jit-compatible (config static) and vmap-able over
windows; `estimate_sequence` scans a full sequence with warm starts.

The returned trace carries everything the energy/latency model (energy.py)
needs: per-stage engine-pass counts and retained-event counts.

Device scopes (`jax.named_scope`, metadata only: they name the compiled
program's operations in a profile and change nothing XLA fuses), in both
the per-window and the lockstep-batched path:

    cmax.stage{k}          one stage's residence
      cmax.sort            the stage-entry `sort_events`
      cmax.engine_pass     each engine call, entry pass and loop body
      cmax.update          the CG-PR / gradient step
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import cgpr
from .adaptive import should_stay
from .contrast import gaussian_taps, stats_to_objective, streaming_stats
from .iwe import build_iwe
from .sorting import sort_events
from .types import Camera, CmaxConfig, EventWindow, StageConfig


class StageTrace(NamedTuple):
    iters: jax.Array        # () int32 — update iterations executed
    passes: jax.Array       # () int32 — engine passes (= iters + entry pass)
    n_retained: jax.Array   # () int32 — events retained by Alg. 3
    v_final: jax.Array      # () f32  — variance at stage exit
    v_entry: jax.Array      # () f32  — variance at stage entry
    v_history: jax.Array    # (max_iters,) f32 padded per-iteration variance
    omega_entry: jax.Array  # (3,) hypothesis at stage entry (sort reference)
    omega_exit: jax.Array   # (3,) hypothesis at stage exit
    spilled: jax.Array      # () int32 — taps over the megakernel's slab
    #                         capacity, summed over the stage's passes; each
    #                         such pass took the exact slow path (always 0
    #                         on the other engines)


class WindowResult(NamedTuple):
    omega: jax.Array                    # (3,) final estimate
    stages: Tuple[StageTrace, ...]      # one per stage


EnginePass = Callable[[EventWindow, jax.Array, jax.Array],
                      Tuple[jax.Array, jax.Array]]


def make_engine_pass(cam: Camera, stage: StageConfig, dtype=jnp.float32,
                     engine: str = "reference", *,
                     capacity: int = 4096) -> EnginePass:
    """One full engine pass at stage s: warp+vote+accumulate (IWE & dIWE),
    streaming blur statistics, Eq. 12 objective + gradient.

    `engine` selects the backend (types.ENGINES): "reference" is the
    pure-jnp oracle datapath; "pallas" (and, per-window, "pallas_batched")
    routes through the fused Pallas kernel path (the batched megakernel
    itself is `make_batched_engine_pass`). Returns
    fn(ev, weights, omega) -> (variance, grad(3,)).
    """
    Hs, Ws = stage.grid(cam)

    if engine in ("pallas", "pallas_batched"):
        # lazy import: kernels -> core.{contrast,geometry,iwe,types} must
        # not re-enter core/__init__ while it is still executing
        from repro.kernels import fused_engine_pass

        def kernel_engine(ev: EventWindow, weights: jax.Array,
                          omega: jax.Array):
            # iwe_accum adds its spilled taps through a scatter, so the
            # pass is exact and the count is not needed here
            v, g, _ = fused_engine_pass(
                ev, omega, cam, stage.scale, stage.blur_taps,
                stage.blur_sigma, weights=weights, capacity=capacity)
            return v, g

        return kernel_engine

    taps = gaussian_taps(stage.blur_taps, stage.blur_sigma, dtype)

    def reference_engine(ev: EventWindow, weights: jax.Array,
                         omega: jax.Array):
        channels = build_iwe(ev, omega, cam, stage.scale, weights=weights)
        stats = streaming_stats(channels, taps)
        return stats_to_objective(stats, Hs * Ws)

    return reference_engine


def make_batched_engine_pass(cam: Camera, stage: StageConfig,
                             cfg: CmaxConfig):
    """Whole-batch megakernel engine pass (engine="pallas_batched"): ONE
    pallas_call whose grid carries the batch axis (kernels/megakernel.py).
    fn(ev (B,N), weights (B,N), omega (B,3)) -> (variance (B,),
    grad (B,3), spilled (B,) int32)."""
    from repro.kernels import batched_engine_pass

    def megakernel_engine(ev: EventWindow, weights: jax.Array,
                          omega: jax.Array):
        return batched_engine_pass(
            ev, omega, cam, stage.scale, stage.blur_taps, stage.blur_sigma,
            weights=weights, rb=cfg.engine_rb, capacity=cfg.engine_capacity,
            dtype=cfg.dtype)

    return megakernel_engine


def _make_engine_for(cfg: CmaxConfig, cam: Camera,
                     stage: StageConfig) -> EnginePass:
    """Per-window engine honouring the config's backend selection."""
    return make_engine_pass(cam, stage, cfg.dtype, engine=cfg.engine,
                            capacity=cfg.engine_capacity)


def _run_stage(ev: EventWindow, omega: jax.Array, opt_state: cgpr.CgprState,
               cam: Camera, stage: StageConfig, cfg: CmaxConfig,
               stage_idx: int, engine: EnginePass,
               iter_cap: Optional[jax.Array] = None
               ) -> Tuple[jax.Array, cgpr.CgprState, StageTrace]:
    """Residence at one stage under Alg. 1 (or the fixed schedule).

    `iter_cap`, when given, is a traced int32 scalar bounding residence on
    top of the static `max_iters` — the hook the budget scheduler
    (costmodel, DESIGN.md §5) uses to spend an energy/latency budget
    without recompiling per allocation."""
    with jax.named_scope("cmax.sort"):
        tables = sort_events(ev, omega, cam, stage)
    weights = tables.weights

    # Alg. 1 line 2: V_prev <- V_s(omega)  (entry pass, also primes grad)
    with jax.named_scope("cmax.engine_pass"):
        v_entry, g_entry = engine(ev, weights, omega)

    if cfg.adaptive:
        max_iters = stage.max_iters
    else:
        max_iters = int(cfg.fixed_iters[stage_idx])
    if iter_cap is None:
        cap = jnp.int32(max_iters)
    else:
        cap = jnp.minimum(jnp.int32(max_iters),
                          jnp.asarray(iter_cap, jnp.int32))

    update = cgpr.step if cfg.use_cgpr else cgpr.gradient_ascent_step
    alpha0 = jnp.asarray(cfg.step_size * stage.step_scale, cfg.dtype)
    alpha_floor = alpha0 / 64.0

    # Update(omega, s) is made robust with accept/reject step control: a
    # proposal that *decreases* the variance is rejected (omega reverts) and
    # the step halves — the Alg. 1 gain test then only sees accepted
    # improvements, as it does on the prototype (whose CG-PR update is
    # well-behaved at its operating step sizes). A stage gives up and
    # promotes when the step has collapsed to alpha0/64. Every proposal,
    # accepted or not, costs one engine pass and is counted as one.

    def cond(carry):
        _, _, _, it, done, _, _ = carry
        return (~done) & (it < cap)

    def body(carry):
        st, v_prev, g, it, _, hist, alpha = carry
        om, ost = st
        with jax.named_scope("cmax.update"):
            om_p, ost_p = update(om, g, ost, alpha)      # propose
        with jax.named_scope("cmax.engine_pass"):
            v_p, g_p = engine(ev, weights, om_p)         # one engine pass
        hist = hist.at[it].set(v_p)
        improved = v_p > v_prev
        sel = lambda a, b: jax.tree.map(
            lambda x, y: jnp.where(improved, x, y), a, b)
        om = sel(om_p, om)
        ost = sel(ost_p, ost)
        g = sel(g_p, g)
        if cfg.adaptive:
            g_norm = (v_p - v_prev) / jnp.maximum(jnp.abs(v_prev), 1e-12)
            done_ok = improved & (g_norm < stage.tau)      # saturated
        else:
            done_ok = jnp.bool_(False)
        alpha = jnp.where(improved, alpha, alpha * 0.5)
        done_stuck = (~improved) & (alpha < alpha_floor) if cfg.adaptive \
            else jnp.bool_(False)
        v_prev = jnp.where(improved, v_p, v_prev)
        return ((om, ost), v_prev, g, it + 1, done_ok | done_stuck,
                hist, alpha)

    hist0 = jnp.full((max_iters,), jnp.nan, dtype=v_entry.dtype)
    (om, ost), v_fin, _, iters, _, hist, _ = jax.lax.while_loop(
        cond, body,
        ((omega, opt_state), v_entry, g_entry, jnp.int32(0),
         jnp.bool_(False), hist0, alpha0))

    trace = StageTrace(iters=iters, passes=iters + 1,
                       n_retained=tables.n_retained, v_final=v_fin,
                       v_entry=v_entry, v_history=hist,
                       omega_entry=omega, omega_exit=om,
                       spilled=jnp.zeros((), jnp.int32))
    return om, ost, trace


def _masked_select(mask: jax.Array, new, old):
    """Per-leaf `where` with a (B,) mask broadcast over trailing axes."""
    return jax.tree.map(
        lambda n, o: jnp.where(
            mask.reshape(mask.shape + (1,) * (n.ndim - 1)), n, o), new, old)


def _run_stage_batched(ev: EventWindow, omega: jax.Array,
                       opt_state: cgpr.CgprState, cam: Camera,
                       stage: StageConfig, cfg: CmaxConfig, stage_idx: int,
                       engine_b, iter_cap: Optional[jax.Array] = None
                       ) -> Tuple[jax.Array, cgpr.CgprState, StageTrace]:
    """`_run_stage` for a whole (B,·) batch in masked lockstep.

    The batched megakernel computes ALL windows' engine passes in one
    pallas_call, so the residence loop cannot be an independent per-window
    while_loop under vmap — instead one shared while_loop keeps iterating
    until every window is done, with finished windows contributing masked
    no-ops (exactly the carry-select semantics JAX's vmap-of-while_loop
    batching rule produces, so traces match the vmapped reference
    bit-for-bit). `iter_cap`, when given, is (B,) int32."""
    B = omega.shape[0]
    with jax.named_scope("cmax.sort"):
        tables = jax.vmap(lambda x, y, t, p, vl, om: sort_events(
            EventWindow(x, y, t, p, vl), om, cam, stage))(
            ev.x, ev.y, ev.t, ev.p, ev.valid, omega)
    weights = tables.weights                              # (B, N)

    with jax.named_scope("cmax.engine_pass"):
        v_entry, g_entry, spill_entry = engine_b(ev, weights, omega)

    if cfg.adaptive:
        max_iters = stage.max_iters
    else:
        max_iters = int(cfg.fixed_iters[stage_idx])
    if iter_cap is None:
        cap = jnp.full((B,), max_iters, jnp.int32)
    else:
        cap = jnp.minimum(jnp.int32(max_iters),
                          jnp.asarray(iter_cap, jnp.int32))

    update = jax.vmap(cgpr.step if cfg.use_cgpr
                      else cgpr.gradient_ascent_step)
    alpha0 = jnp.asarray(cfg.step_size * stage.step_scale, cfg.dtype)
    alpha_floor = alpha0 / 64.0
    rows = jnp.arange(B)

    def cond(carry):
        _, _, _, it, done, _, _, _ = carry
        return jnp.any((~done) & (it < cap))

    def body(carry):
        st, v_prev, g, it, done, hist, alpha, spill = carry
        active = (~done) & (it < cap)                     # (B,)
        om, ost = st
        with jax.named_scope("cmax.update"):
            om_p, ost_p = update(om, g, ost, alpha)       # propose (all B)
        with jax.named_scope("cmax.engine_pass"):         # ONE kernel launch
            v_p, g_p, spill_p = engine_b(ev, weights, om_p)
        it_c = jnp.clip(it, 0, max_iters - 1)
        hist = hist.at[rows, it_c].set(
            jnp.where(active, v_p, hist[rows, it_c]))
        improved = v_p > v_prev
        om_n = _masked_select(improved, om_p, om)
        ost_n = _masked_select(improved, ost_p, ost)
        g_n = _masked_select(improved, g_p, g)
        if cfg.adaptive:
            g_norm = (v_p - v_prev) / jnp.maximum(jnp.abs(v_prev), 1e-12)
            done_ok = improved & (g_norm < stage.tau)
        else:
            done_ok = jnp.zeros((B,), bool)
        alpha_n = jnp.where(improved, alpha, alpha * 0.5)
        done_stuck = (~improved) & (alpha_n < alpha_floor) if cfg.adaptive \
            else jnp.zeros((B,), bool)
        v_prev_n = jnp.where(improved, v_p, v_prev)
        # finished windows keep their carry verbatim (masked no-op)
        new = ((om_n, ost_n), v_prev_n, g_n, it + 1,
               done_ok | done_stuck, hist, alpha_n, spill + spill_p)
        return _masked_select(active, new, carry)

    hist0 = jnp.full((B, max_iters), jnp.nan, dtype=v_entry.dtype)
    (om, ost), v_fin, _, iters, _, hist, _, spilled = jax.lax.while_loop(
        cond, body,
        ((omega, opt_state), v_entry, g_entry,
         jnp.zeros((B,), jnp.int32), jnp.zeros((B,), bool), hist0,
         jnp.full((B,), alpha0, cfg.dtype), spill_entry))

    trace = StageTrace(iters=iters, passes=iters + 1,
                       n_retained=tables.n_retained, v_final=v_fin,
                       v_entry=v_entry, v_history=hist,
                       omega_entry=omega, omega_exit=om, spilled=spilled)
    return om, ost, trace


def _estimate_batch_lockstep(windows: EventWindow, omega0s: jax.Array,
                             cfg: CmaxConfig,
                             iter_caps: Optional[jax.Array] = None
                             ) -> WindowResult:
    """Whole-batch estimation through the batched engine pass: every engine
    pass of every stage is ONE megakernel launch covering the full batch."""
    cam = cfg.camera
    B = omega0s.shape[0]
    omega = omega0s.astype(cfg.dtype)
    traces = []
    for si, stage in enumerate(cfg.stages):
        engine_b = make_batched_engine_pass(cam, stage, cfg)
        # CG restarts at each stage, as in the per-window path.
        opt_state = jax.vmap(lambda _: cgpr.init_state(3, cfg.dtype))(
            jnp.arange(B))
        with jax.named_scope(f"cmax.stage{si}"):
            omega, opt_state, tr = _run_stage_batched(
                windows, omega, opt_state, cam, stage, cfg, si, engine_b,
                iter_cap=None if iter_caps is None else iter_caps[:, si])
        traces.append(tr)
    return WindowResult(omega=omega, stages=tuple(traces))


def _map_slots(fn, windows: EventWindow, *per_slot) -> WindowResult:
    """Batch entry for the engines that do not run in lockstep: `lax.map`
    runs fn(window, *slot_args) on each slot in turn, so every window
    keeps its own stage `while_loop`s and B = 1 engine passes, and no slot
    waits on another's residence. `per_slot` are (B, ...) operands mapped
    beside the windows (warm starts, iteration caps)."""
    return jax.lax.map(lambda a: fn(*a), (windows,) + per_slot)


@functools.partial(jax.jit, static_argnames=("cfg",))
def estimate_window(ev: EventWindow, omega0: jax.Array,
                    cfg: CmaxConfig) -> WindowResult:
    """Estimate the rotation rate for one event window (warm-started)."""
    if cfg.engine == "pallas_batched":
        # B=1 batch through the megakernel path, squeezed back to scalars.
        res = _estimate_batch_lockstep(
            jax.tree.map(lambda a: a[None], ev), omega0[None], cfg)
        return jax.tree.map(lambda a: jnp.squeeze(a, 0), res)
    cam = cfg.camera
    omega = omega0.astype(cfg.dtype)
    opt_state = cgpr.init_state(3, cfg.dtype)
    traces = []
    for si, stage in enumerate(cfg.stages):
        engine = _make_engine_for(cfg, cam, stage)
        # CG history does not transfer across resolutions (the objective
        # surface changes scale) — restart CG at each stage, as HW does.
        opt_state = cgpr.init_state(3, cfg.dtype)
        with jax.named_scope(f"cmax.stage{si}"):
            omega, opt_state, tr = _run_stage(ev, omega, opt_state, cam,
                                              stage, cfg, si, engine)
        traces.append(tr)
    return WindowResult(omega=omega, stages=tuple(traces))


@functools.partial(jax.jit, static_argnames=("cfg",))
def estimate_window_budgeted(ev: EventWindow, omega0: jax.Array,
                             iter_caps: jax.Array, cfg: CmaxConfig
                             ) -> WindowResult:
    """`estimate_window` under a per-stage iteration allocation.

    `iter_caps` is an (n_stages,) int32 array of caps from the budget
    scheduler (costmodel.BudgetScheduler, DESIGN.md §5). Caps are traced
    data: one executable serves every allocation. The adaptive gain test
    still terminates a stage early — the cap only bounds how much a stage
    is ALLOWED to iterate; caps >= stage.max_iters reproduce
    `estimate_window` exactly."""
    if cfg.engine == "pallas_batched":
        res = _estimate_batch_lockstep(
            jax.tree.map(lambda a: a[None], ev), omega0[None], cfg,
            iter_caps=iter_caps[None])
        return jax.tree.map(lambda a: jnp.squeeze(a, 0), res)
    cam = cfg.camera
    omega = omega0.astype(cfg.dtype)
    opt_state = cgpr.init_state(3, cfg.dtype)
    traces = []
    for si, stage in enumerate(cfg.stages):
        engine = _make_engine_for(cfg, cam, stage)
        opt_state = cgpr.init_state(3, cfg.dtype)
        with jax.named_scope(f"cmax.stage{si}"):
            omega, opt_state, tr = _run_stage(ev, omega, opt_state, cam,
                                              stage, cfg, si, engine,
                                              iter_cap=iter_caps[si])
        traces.append(tr)
    return WindowResult(omega=omega, stages=tuple(traces))


@functools.partial(jax.jit, static_argnames=("cfg",),
                   donate_argnames=("omega0s",))
def estimate_batch_budgeted(windows: EventWindow, omega0s: jax.Array,
                            iter_caps: jax.Array, cfg: CmaxConfig
                            ) -> WindowResult:
    """Batched `estimate_window_budgeted` (with the warm-start buffer
    donated, like `estimate_batch_donated`) under a per-window per-stage
    iteration allocation: `iter_caps` is (B, n_stages) int32. The serving
    layer dispatches QoS-budgeted batches through this entry point; like
    the unbudgeted batch path, per-slot results depend only on that slot's
    inputs, so warm-start chains survive arbitrary batch shapes."""
    if cfg.engine == "pallas_batched":
        return _estimate_batch_lockstep(windows, omega0s, cfg,
                                        iter_caps=iter_caps)
    return _map_slots(lambda ev, o, c: estimate_window_budgeted(
        ev, o, c, cfg), windows, omega0s, iter_caps)


def estimate_sequence(windows: EventWindow, omega_init: jax.Array,
                      cfg: CmaxConfig) -> Tuple[jax.Array, WindowResult]:
    """Sequential estimation over a batch of windows with warm starts.

    `windows` arrays have a leading window axis (K, N). Returns
    (omegas (K,3), stacked WindowResult traces).
    """
    def scan_fn(omega, win_slice):
        ev = EventWindow(*win_slice)
        res = estimate_window(ev, omega, cfg)
        return res.omega, res

    leaves = (windows.x, windows.y, windows.t, windows.p, windows.valid)
    omega_fin, results = jax.lax.scan(scan_fn, omega_init, leaves)
    return results.omega, results


def estimate_windows_parallel(windows: EventWindow, omega0s: jax.Array,
                              cfg: CmaxConfig) -> WindowResult:
    """Batched estimation of independent windows (no warm-start chaining) —
    the building block for data-parallel multi-device CMAX (distributed.py).

    Under engine="pallas_batched" the whole batch runs in masked lockstep
    with one megakernel launch per engine pass; on the other engines each
    slot runs `estimate_window` on its own, one after another
    (`_map_slots`)."""
    if cfg.engine == "pallas_batched":
        return _estimate_batch_lockstep(windows, omega0s, cfg)
    return _map_slots(lambda ev, o: estimate_window(ev, o, cfg),
                      windows, omega0s)


@functools.partial(jax.jit, static_argnames=("cfg",))
def estimate_batch(windows: EventWindow, omega0s: jax.Array,
                   cfg: CmaxConfig) -> WindowResult:
    """Batched estimation of B independent windows — the serving hot path.

    `windows` arrays have shape (B, N) with padded slots carrying
    valid=False; `omega0s` is (B, 3) of per-window warm starts. One compiled
    executable exists per (B, N, cfg) triple — the serving layer
    (launch/serve.py) bounds that set by bucketing N and B into length
    classes (DESIGN.md §4). On the "reference" and "pallas" engines each
    slot runs its own adaptive while_loops (`lax.map` over slots): a
    window that saturates early ends its residence and the next slot
    starts. Only "pallas_batched", whose engine pass covers the whole
    batch, runs the windows in masked lockstep, finished windows
    contributing masked no-ops until the slowest is done (the SIMT analog
    of the controller's clock gating). Per-window true iteration counts
    survive in the returned traces on every engine.
    """
    return estimate_windows_parallel(windows, omega0s, cfg)


@functools.partial(jax.jit, static_argnames=("cfg",),
                   donate_argnames=("omega0s",))
def estimate_batch_donated(windows: EventWindow, omega0s: jax.Array,
                           cfg: CmaxConfig) -> WindowResult:
    """`estimate_batch` with the warm-start buffer donated to XLA.

    The async serving loop (launch/serve.py) dispatches a fresh (B, 3)
    warm-start array per batch and never reads it back — donating it lets
    XLA reuse the buffer in place, so continuous refill does not
    accumulate live (B, 3) staging buffers while several batches are in
    flight. Dispatch is asynchronous (JAX's default): the returned arrays
    are futures; callers poll readiness (`jax.Array.is_ready`) or block.

    Per-slot results depend only on that slot's window and warm start —
    by construction on the non-lockstep engines, where each slot runs
    `estimate_window` alone, and through the carry-select masking of the
    lockstep megakernel path — so a stream's warm-start chain is preserved
    bit-for-bit no matter which in-flight batch, slot position, or fill
    pattern its windows land in. That invariant is what lets the service
    refill finished slots out of order (tests/test_serving_async.py pins
    it).
    """
    return estimate_windows_parallel(windows, omega0s, cfg)


def estimate_streams(windows: EventWindow, omega_inits: jax.Array,
                     cfg: CmaxConfig) -> Tuple[jax.Array, WindowResult]:
    """Warm-start-chained estimation of S independent streams.

    `windows` arrays have shape (S, K, N): S concurrent streams of K
    windows each; `omega_inits` is (S, 3). Within each stream the windows
    are processed sequentially with warm-start chaining (scan); across
    streams everything is batched (vmap) — so this composes the accuracy
    of `estimate_sequence` with the throughput of `estimate_batch`.
    Returns (omegas (S, K, 3), stacked traces).
    """
    if cfg.engine == "pallas_batched":
        # scan over the K window positions; at each step the S concurrent
        # streams are one megakernel batch. Per-slot independence of the
        # lockstep path keeps each stream's warm-start chain identical to
        # running it alone (tests/test_megakernel_properties.py pins it).
        def scan_fn(omega_s, win_slice):
            res = _estimate_batch_lockstep(EventWindow(*win_slice),
                                           omega_s, cfg)
            return res.omega, res

        leaves = tuple(jnp.swapaxes(a, 0, 1) for a in (
            windows.x, windows.y, windows.t, windows.p, windows.valid))
        _, results = jax.lax.scan(scan_fn, omega_inits.astype(cfg.dtype),
                                  leaves)
        results = jax.tree.map(lambda a: jnp.swapaxes(a, 0, 1), results)
        return results.omega, results

    def one_stream(x, y, t, p, v, omega0):
        return estimate_sequence(EventWindow(x, y, t, p, v), omega0, cfg)

    return jax.vmap(one_stream)(windows.x, windows.y, windows.t, windows.p,
                                windows.valid, omega_inits)


def measured_stage_gains(result: WindowResult) -> np.ndarray:
    """Measured whole-residence variance gain per stage, (B, S) float64:

        (v_final - v_entry) / (|v_entry| + eps)        (Eq. 7 numerator
                                                        over the entry
                                                        variance scale)

    Accepts both single-window results (scalar traces -> B = 1) and
    batched results ((B,) traces). Telemetry-only: runs on harvested
    host values, never inside a jit trace.
    """
    cols = []
    for st in result.stages:
        ve = np.atleast_1d(np.asarray(st.v_entry, np.float64))
        vf = np.atleast_1d(np.asarray(st.v_final, np.float64))
        cols.append((vf - ve) / (np.abs(ve) + 1e-12))
    return np.stack(cols, axis=1) if cols else np.zeros((1, 0))
