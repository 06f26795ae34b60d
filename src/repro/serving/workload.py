"""The `Workload` plugin interface for the batched estimation services.

CMAX-CAMEL's thesis is that execution policy (admission, bucketing,
continuous refill, deadline shedding, QoS budgets) co-designs with data
movement *independently of any one workload* — the same point the
unifying-framework view makes on the algorithm side: the pipeline is
generic, only the warp/workload model varies. This module is that split
made concrete. The services in `repro.launch.serve` own the scheduler
state machine and the executable cache; a `Workload` owns everything the
scheduler must not know:

  * **bucketing** — mapping a request payload to a padded length class
    (`bucket_of`), so the compiled-executable set is bounded by policy;
  * **batch materialization** — padding + leader-replicated fill into a
    `(batch_b, bucket_n)` batch plus the stacked per-stream carried
    state (`make_batch`);
  * **the executable factory** — one compiled batch function per
    (bucket, batch, flags) class (`executable`);
  * **per-stream carried state** — the CMAX warm-start omega today, the
    LM per-stream KV/recurrent cache here too (`default_state`,
    harvested state re-enters the next window's batch);
  * **QoS budget allocation** — turning per-window joule/ms budgets into
    per-slot caps, where the workload supports it (`allocate_caps`);
  * **harvest** — slicing a finished batch back into per-slot outputs,
    new carried states, iteration counts, and measured gain.

The scheduler's invariants (per-stream FIFO with carried state under any
completion order, bitwise slot independence at fixed batch size,
deadline shedding, executable-cache hit accounting) are workload
contracts, pinned for every plugin by
`tests/test_workload_conformance.py` — a new workload is servable when
it passes that suite.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np


class SlotResult(NamedTuple):
    """One harvested batch slot."""
    output: object            # response payload (CMAX: omega (3,); LM: tokens)
    state: object             # carried per-stream state for the next window
    iters: Tuple[int, ...]    # per-stage iteration counts (workload-defined)
    gain: Optional[float]     # measured gain for the budget feedback loop


class Workload:
    """Base interface; every method the services call is defined here.

    Subclasses must set `name` and `policy` (an object with
    ``bucket_of(n) -> int`` and ``classes(n_min, n_max)``, e.g.
    `repro.data.events.BucketPolicy` — the policy is count-generic:
    events for CMAX, tokens for LM) and implement the abstract methods.
    """

    name: str = "workload"
    #: whether budgeted QoS classes are servable (allocate_caps is real)
    supports_budgets: bool = False
    policy = None

    @property
    def budget_unsupported_msg(self) -> str:
        """Raised by the service when budgeted QoS classes are configured
        but this workload cannot serve them."""
        return (f"workload {self.name!r} does not support budgeted "
                f"QoS classes")

    # -- request side --------------------------------------------------------

    def bucket_of(self, payload) -> int:
        """Length class of one payload; must raise for unservable sizes
        (a poison request must never sit in the queue)."""
        return self.policy.bucket_of(self.size_of(payload))

    def size_of(self, payload) -> int:
        """Raw slot count of a payload (events / tokens) — the numerator
        of the service's padding accounting."""
        return payload.n

    def coerce_hint(self, hint):
        """Normalize a submitted carried-state override."""
        return hint

    # -- carried state -------------------------------------------------------

    def default_state(self):
        """Carried state for a stream's first window."""
        raise NotImplementedError

    def shed_output(self, state):
        """Response payload for a shed request (state is the stream's last
        harvested state, or None for a fresh stream)."""
        raise NotImplementedError

    # -- batch materialization / execution ----------------------------------

    def make_batch(self, payloads: Sequence, states: Sequence,
                   bucket_n: int, batch_b: int) -> Tuple[object, object, int]:
        """Pad payloads to (batch_b, bucket_n) and stack carried states;
        fill slots replicate the batch leader (finite well-formed data,
        results discarded). Returns (data_batch, state_batch, n_fill)."""
        raise NotImplementedError

    def executable(self, bucket_n: int, batch_b: int, *,
                   budgeted: bool = False, donate: bool = True) -> Callable:
        """The batch function for one (length, batch) class:
        fn(data_batch, state_batch) -> result. Must be cacheable by the
        service per (bucket_n, batch_b, budgeted) key — repeat classes
        never retrace."""
        raise NotImplementedError

    # -- QoS budgets ---------------------------------------------------------

    def allocate_caps(self, requests: Sequence, batch_b: int,
                      qos_classes: Dict, gains: Dict,
                      stats: Dict) -> Optional[np.ndarray]:
        """Per-slot work caps for one formed batch, or None when every
        member is standard. Only called when the service has budgeted QoS
        classes; the base workload does not support those."""
        raise NotImplementedError(
            f"workload {self.name!r} does not support budgeted QoS classes")

    def attach_caps(self, fn: Callable, caps: np.ndarray) -> Callable:
        """Close a cap allocation over a budgeted executable so every
        executor sees the uniform fn(data, state) submit signature."""
        raise NotImplementedError

    # -- telemetry -----------------------------------------------------------

    def decision_meta(self, result) -> Optional[dict]:
        """Per-stage decision-log metadata for one harvested batch result
        (`repro.telemetry.DecisionLog`): a dict with

            "gains"     — (B, S) measured whole-residence gain per stage
            "max_iters" — (S,) static per-stage iteration bounds

        or None when the workload has no per-stage objective (decision
        records then carry gain=None / max_iters=None). Only called when
        decision logging is enabled — must not burden the default path."""
        return None

    def unaffordable(self, payload, qos, gain0=None) -> bool:
        """Strict-QoS admission test: True when even the floor execution
        of `payload` is modelled to exceed the class's per-window budget
        (such requests are refused at submit, not overspent on). The base
        workload has no cost model and never refuses."""
        return False

    # -- harvest -------------------------------------------------------------

    def harvest(self, result, track_gain: bool) -> Callable[[int], SlotResult]:
        """Batch-level harvest: returns slot(i) -> SlotResult. Per-slot
        results must depend only on that slot's inputs (the refill
        invariant); `track_gain` asks for the measured-gain feedback the
        budget scheduler consumes (None when unavailable)."""
        raise NotImplementedError

    def null_result(self, bucket_n: int, batch_b: int):
        """A harvest-compatible stand-in result for data-free executors
        (the virtual-time DES drives the scheduler with no array work)."""
        raise NotImplementedError

    def spilled_taps(self, result, n_real: int) -> int:
        """Work the batch's fast path could not hold and recomputed on an
        exact slow path, summed over the first `n_real` (non-fill) slots;
        0 for a workload without such a path."""
        return 0

    def engine_passes(self, iters: Tuple[int, ...]) -> Tuple[int, ...]:
        """Engine passes per stage of one window that ran `iters` (the
        harvested slot's count), for the service's pass counters; () for
        a workload without engine passes."""
        return ()

    @property
    def lockstep(self) -> bool:
        """Whether the executable runs a batch's slots in lockstep, every
        slot passing as often per stage as the slowest window; otherwise
        each slot runs only its own passes. Read by the service's pass
        counters."""
        return True


# ---------------------------------------------------------------------------
# CMAX: the paper's contrast-maximization pipeline as a plugin.
# ---------------------------------------------------------------------------


class CmaxWorkload(Workload):
    """Contrast-maximization estimation over variable-length event
    windows — the original service behavior, verbatim: payloads are 1-D
    `EventWindow`s, carried state is the (3,) warm-start omega, the
    executable is the jitted `estimate_batch*` family, and budgeted QoS
    classes run under `costmodel.BudgetScheduler` iteration caps. The
    refactored service dispatching through this plugin is bitwise
    equivalent to the pre-plugin path (tests/test_serving_async.py and
    the megakernel refill invariants pass unmodified)."""

    name = "cmax"

    def __init__(self, cfg, policy=None, mesh=None, scheduler=None):
        from repro.data import events as ev_data
        self.cfg = cfg
        self.policy = policy or ev_data.pow2_policy(min_bucket=512)
        self.mesh = mesh
        self._scheduler = scheduler     # costmodel.BudgetScheduler (lazy)

    @property
    def supports_budgets(self) -> bool:
        # estimate_batch_sharded has no budgeted variant yet
        return self.mesh is None

    @property
    def budget_unsupported_msg(self) -> str:
        return ("budgeted QoS classes are not supported with a "
                "mesh (estimate_batch_sharded has no budgeted "
                "variant yet)")

    # -- request side --------------------------------------------------------

    def coerce_hint(self, hint):
        return None if hint is None else np.asarray(hint, np.float32)

    # -- carried state -------------------------------------------------------

    def default_state(self):
        return np.zeros(3, np.float32)

    def shed_output(self, state):
        return self.default_state() if state is None else state

    # -- batch materialization / execution ----------------------------------

    def make_batch(self, payloads, states, bucket_n, batch_b):
        import jax.numpy as jnp
        from repro.data import events as ev_data

        omega0 = list(states)
        omega0 += [omega0[0]] * (batch_b - len(omega0))
        ev_batch, n_fill = ev_data.fill_batch(list(payloads), bucket_n,
                                              batch_b)
        om_batch = jnp.asarray(np.stack(omega0))
        return ev_batch, om_batch, n_fill

    def executable(self, bucket_n, batch_b, *, budgeted=False, donate=True):
        from repro.core.pipeline import (estimate_batch,
                                         estimate_batch_budgeted,
                                         estimate_batch_donated)

        cfg = self.cfg
        if self.mesh is not None:
            from repro.core.distributed import estimate_batch_sharded
            mesh = self.mesh
            return lambda w, o: estimate_batch_sharded(w, o, cfg, mesh)
        if budgeted:
            return lambda w, o, caps: estimate_batch_budgeted(w, o, caps,
                                                              cfg)
        # module-level jitted with static cfg (async: donated warm-start
        # buffer); executables are shared across service instances — the
        # per-key cache entry only tracks which shape classes one service
        # has needed.
        if donate:
            return lambda w, o: estimate_batch_donated(w, o, cfg)
        return lambda w, o: estimate_batch(w, o, cfg)

    # -- QoS budgets ---------------------------------------------------------

    def _budget_scheduler(self):
        if self._scheduler is None:
            from repro.costmodel import BudgetScheduler, load_profile
            self._scheduler = BudgetScheduler(load_profile("paper_fpga_45nm"))
        return self._scheduler

    def allocate_caps(self, requests, batch_b, qos_classes, gains, stats):
        classes = {r.qos: qos_classes[r.qos] for r in requests}
        if not any(q.budgeted for q in classes.values()):
            return None
        sched = self._budget_scheduler()
        S = len(self.cfg.stages)
        uncapped = max(int(s.max_iters) for s in self.cfg.stages)
        caps = np.full((batch_b, S), uncapped, np.int32)
        for name, q in classes.items():
            if not q.budgeted:
                continue
            members = [(i, r) for i, r in enumerate(requests)
                       if r.qos == name]
            plans = [sched.plan_window(self.cfg, r.window.n,
                                       gain0=gains.get(r.stream_id))
                     for _, r in members]
            alloc = sched.allocate(
                plans,
                budget_uj=None if q.budget_uj is None
                else q.budget_uj * len(members),
                budget_ms=None if q.budget_ms is None
                else q.budget_ms * len(members))
            for j, (i, _) in enumerate(members):
                caps[i] = alloc.iters[j]
            stats["budgeted_windows"] += len(members)
            if np.isfinite(alloc.spent_uj):
                stats["budget_spent_uj"] += alloc.spent_uj
        # fill slots replicate the leader's data and are discarded — cap
        # them at the 1-iteration floor so they buy no wasted refinement
        caps[len(requests):, :] = 1
        return caps

    def attach_caps(self, fn, caps):
        import jax.numpy as jnp
        caps_arr = jnp.asarray(caps)
        return (lambda _fn, _c: lambda w, o: _fn(w, o, _c))(fn, caps_arr)

    # -- telemetry -----------------------------------------------------------

    def decision_meta(self, result):
        stages = getattr(result, "stages", ())
        if not stages:
            return None
        from repro.core.pipeline import measured_stage_gains
        cfg = self.cfg
        max_iters = tuple(
            int(st.max_iters) if cfg.adaptive else int(cfg.fixed_iters[si])
            for si, st in enumerate(cfg.stages))
        return {"gains": measured_stage_gains(result),
                "max_iters": max_iters}

    def unaffordable(self, payload, qos, gain0=None):
        if not getattr(qos, "strict", False) or not qos.budgeted:
            return False
        sched = self._budget_scheduler()
        plan = sched.plan_window(self.cfg, payload.n, gain0=gain0)
        return not sched.affordable(plan, budget_uj=qos.budget_uj,
                                    budget_ms=qos.budget_ms)

    # -- harvest -------------------------------------------------------------

    def harvest(self, result, track_gain):
        omegas = np.asarray(result.omega)
        stages = getattr(result, "stages", ())
        iters = [np.asarray(tr.iters) for tr in stages]
        if track_gain and stages:
            v_ent = [np.asarray(tr.v_entry) for tr in stages]
            v_fin = [np.asarray(tr.v_final) for tr in stages]

        def slot(i: int) -> SlotResult:
            om = omegas[i]
            gain = None
            if track_gain and stages:
                # measured Eq. 7 gain per accepted iteration, averaged over
                # stages — feeds the scheduler's gain model for this
                # stream's NEXT window (closing measurement -> allocation)
                g = [(vf[i] - ve[i]) / ((abs(ve[i]) + 1e-12)
                                        * max(int(it[i]), 1))
                     for ve, vf, it in zip(v_ent, v_fin, iters)]
                gain = max(float(np.mean(g)), 0.0)
            return SlotResult(om, om, tuple(int(it[i]) for it in iters),
                              gain)
        return slot

    def null_result(self, bucket_n, batch_b):
        import types
        return types.SimpleNamespace(
            omega=np.zeros((batch_b, 3), np.float32), stages=())

    def spilled_taps(self, result, n_real):
        # megakernel taps over the per-slab capacity (StageTrace.spilled)
        return int(sum(np.asarray(tr.spilled)[:n_real].sum()
                       for tr in getattr(result, "stages", ())))

    def engine_passes(self, iters):
        # the stage's entry pass plus one per iteration (StageTrace.passes)
        return tuple(int(it) + 1 for it in iters)

    @property
    def lockstep(self) -> bool:
        # only the megakernel's pass covers the whole batch
        # (core/pipeline.py: the other engines map over slots)
        return self.cfg.engine == "pallas_batched"


# ---------------------------------------------------------------------------
# LM decode: variable-length token chunks, per-stream KV state carried
# across windows — the same serving shape as CMAX streams.
# ---------------------------------------------------------------------------


class LMChunkResult(NamedTuple):
    """One served chunk batch: argmax next-token predictions per real
    position (-1 in pad slots), the real lengths, the advanced per-stream
    caches, and (optionally) the per-position logits."""
    tokens: object           # (B, bucket_n) int32, -1 beyond each length
    lens: object             # (B,) int32
    state: object            # stacked per-stream {"cache": ...} pytrees
    logits: object = None    # (B, bucket_n, V) f32 when requested


class LMDecodeWorkload(Workload):
    """LM decode served in variable-length chunks through the bucketed
    service.

    A request payload is a `TokenChunk` (repro.data.lm): the next L
    observed tokens of one stream. Serving a chunk runs L single-token
    decode steps against the stream's carried KV/recurrent cache
    (teacher-forced continuation — step t consumes token t and predicts
    token t+1), then carries the advanced cache to the stream's next
    chunk, exactly as CMAX carries warm-start omegas. L is padded to the
    policy's token-length class; pad steps run masked no-ops (the carry
    is kept verbatim, mirroring the lockstep-batch select semantics of
    `_run_stage_batched`), so padded positions never advance the cache
    nor influence any real position's logits. The batch axis is `vmap`
    over a single-stream chunk scan, so per-slot results depend only on
    that slot's inputs — the bitwise slot-independence the service's
    out-of-order refill relies on.
    """

    name = "lm_decode"
    supports_budgets = False
    PAD_TOKEN = 0            # pad input id (never influences real outputs)

    def __init__(self, model_cfg, params=None, policy=None,
                 max_len: int = 512, return_logits: bool = False,
                 param_seed: int = 0):
        from repro.data import lm as lm_data
        self.cfg = model_cfg
        self.policy = policy or lm_data.chunk_policy()
        self.max_len = int(max_len)
        self.return_logits = bool(return_logits)
        self._params = params
        self._param_seed = param_seed
        self._chunk_fn = None            # lazily built + jitted once
        self._chunk_fn_donated = None

    # -- model plumbing ------------------------------------------------------

    @property
    def params(self):
        if self._params is None:
            import jax
            from repro.models import transformer as tfm
            need_pos = self.cfg.pos_embedding == "learned"
            self._params = tfm.init_params(
                jax.random.key(self._param_seed), self.cfg,
                max_len=self.max_len if need_pos else 0)
        return self._params

    def _build_chunk_fn(self, donate: bool):
        import jax
        import jax.numpy as jnp
        from repro.models import transformer as tfm

        cfg = self.cfg
        params = self.params
        want_logits = self.return_logits

        def one(state, toks, length):
            """One stream's chunk: scan L decode steps with masked no-op
            pad steps. toks (bucket_n,) int32, length () int32."""
            cache = state["cache"]

            def body(c, inp):
                tok, t = inp
                logits, nc = tfm.decode_step(params, cfg,
                                             tok.reshape(1, 1), c)
                # decode_step may emit cache keys the init structure lacks
                # (e.g. "scan": None for unscanned depth plans) — keep the
                # carry structure fixed across steps
                nc = {k: nc.get(k) for k in c}
                active = t < length
                c = jax.tree.map(lambda n, o: jnp.where(active, n, o),
                                 nc, c)
                row = logits[0, -1]                         # (V,) f32
                pred = jnp.argmax(row, axis=-1).astype(jnp.int32)
                out_tok = jnp.where(active, pred, jnp.int32(-1))
                ys = (out_tok, jnp.where(active, row, 0.0)
                      if want_logits else None)
                return c, ys

            steps = (toks, jnp.arange(toks.shape[0], dtype=jnp.int32))
            cache, (preds, rows) = jax.lax.scan(body, cache, steps)
            return {"cache": cache}, preds, rows

        batched = jax.vmap(one)

        def fn(data, state_batch):
            toks, lens = data
            st, preds, rows = batched(state_batch, toks, lens)
            return LMChunkResult(tokens=preds, lens=lens, state=st,
                                 logits=rows)

        if donate:
            return jax.jit(fn, donate_argnums=(1,))
        return jax.jit(fn)

    # -- carried state -------------------------------------------------------

    def default_state(self):
        from repro.models import transformer as tfm
        return {"cache": tfm.init_cache(self.cfg, 1, self.max_len)}

    def shed_output(self, state):
        return np.zeros((0,), np.int32)      # no tokens were decoded

    # -- batch materialization / execution ----------------------------------

    def make_batch(self, payloads, states, bucket_n, batch_b):
        import jax
        import jax.numpy as jnp
        from repro.data import lm as lm_data

        toks, lens, n_fill = lm_data.fill_chunk_batch(
            list(payloads), bucket_n, batch_b, pad_id=self.PAD_TOKEN)
        st = list(states) + [states[0]] * n_fill
        state_batch = jax.tree.map(lambda *xs: jnp.stack(xs), *st)
        return (jnp.asarray(toks), jnp.asarray(lens)), state_batch, n_fill

    def executable(self, bucket_n, batch_b, *, budgeted=False, donate=True):
        if budgeted:
            raise NotImplementedError(
                "LMDecodeWorkload has no budgeted executable")
        if donate:
            if self._chunk_fn_donated is None:
                self._chunk_fn_donated = self._build_chunk_fn(donate=True)
            return self._chunk_fn_donated
        if self._chunk_fn is None:
            self._chunk_fn = self._build_chunk_fn(donate=False)
        return self._chunk_fn

    # -- harvest -------------------------------------------------------------

    def harvest(self, result, track_gain):
        import jax
        toks = np.asarray(result.tokens)
        lens = np.asarray(result.lens)
        state = result.state

        def slot(i: int) -> SlotResult:
            L = int(lens[i])
            out = toks[i, :L].copy()
            new_state = None if state is None else \
                jax.tree.map(lambda a: a[i], state)
            return SlotResult(out, new_state, (L,), None)
        return slot

    def null_result(self, bucket_n, batch_b):
        import types
        return types.SimpleNamespace(
            tokens=np.full((batch_b, bucket_n), -1, np.int32),
            lens=np.zeros((batch_b,), np.int32), state=None)
