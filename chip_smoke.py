"""Bring-up check: serve the paper's CMAX deployment on a TPU.

    python chip_smoke.py               # one chip: phases (a) and (b)
    python chip_smoke.py --chips 4     # four chips: phase (c) only

The deployment is `configs/cmax_camel.py`: a DAVIS240C (240x180), 40,000
events per window, three adaptive coarse-to-fine stages. Four streams of
three windows each are generated from fixed seeds by `data/events.py` and
served through `AsyncBatchedEstimationService`, the service behind
`python -m repro.launch.serve cmax`, with every window padded to one
40,960-event length class.

  (a) engine="reference" (the jnp oracle datapath);
  (b) engine="pallas_batched" (the megakernel, compiled): its program must
      contain a `tpu_custom_call`; no slab may spill at the capacity it
      runs with, neither in the served run nor at fixed omegas; at fixed
      omegas its Eq. 12 objective must match the reference engine's on
      every window and stage within STATS_TOL; and, end to end, its
      estimates must stay within DOMEGA_BOUND of (a);
  (c) --chips 4: the megakernel served on a 4-device `data` mesh, whose
      outputs must be sharded over the four devices and agree with a
      one-chip run of the same windows within DOMEGA_BOUND.

Diagnostics go to stdout. Any failed check raises; the last line, a JSON
object naming the device, is printed only when every phase passed. There
is no CPU fallback: without a TPU the script exits non-zero.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

STREAMS = 4
WINDOWS = 3
BUCKET = 40960          # the one length class every 40,000-event window uses
MAX_BATCH = 8
#: largest |omega_megakernel - omega_reference| (rad/s, any component) the
#: chip may show. The two engines sum in different orders, and the
#: adaptive controller turns last-bit differences into an accepted or
#: rejected step, which the warm-start chain carries to the next window.
#: The CPU rehearsal of these 12 windows (kernels interpreted) differed by
#: at most 0.0408 rad/s (stream s3, window 1; at most 0.003 on the first
#: window of each stream), against an RMSE to ground truth of 0.09 rad/s
#: for either engine. The bound is 2.5x that rehearsal. It only catches a
#: gross failure: the kernel's numerics are held to STATS_TOL.
DOMEGA_BOUND = 0.1
#: largest error of the megakernel's objective against the reference
#: engine's at fixed omega (`objective_error`), over every window and
#: stage. The CPU rehearsal of these windows (ground-truth omegas, and
#: omegas 0.1 rad/s off) read at most 4.98e-6; with the deltas voted in
#: bfloat16 (what a default-precision f32 dot does on the MXU) it read
#: 1.04e-2 to 2.65e-2.
STATS_TOL = 1e-4


def log(msg: str) -> None:
    print(msg, flush=True)


class CompileCounter:
    """Counts XLA backend compiles and persistent-cache hits, through
    `jax.monitoring`."""

    def __init__(self):
        import jax
        from jax._src import dispatch
        self.compiles = 0
        self.cache_hits = 0
        backend_event = dispatch.BACKEND_COMPILE_EVENT

        def on_duration(event, duration, **_):
            if event == backend_event:
                self.compiles += 1

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def snapshot(self):
        return self.compiles, self.cache_hits


def make_streams():
    """{stream id: (list of 1-D windows, (WINDOWS, 3) ground truth)}."""
    from repro.configs import cmax_camel
    from repro.data import events as ev_data
    out = {}
    for s in range(STREAMS):
        spec = ev_data.SequenceSpec(
            name=f"s{s}", n_windows=WINDOWS,
            events_per_window=cmax_camel.EVENTS_PER_WINDOW, seed=100 + s,
            camera=cmax_camel.CAMERA, omega_scale=3.0, window_dt=0.02)
        wins, om_true, _ = ev_data.make_sequence(spec)
        out[f"s{s}"] = ([ev_data.window_slice(wins, k)
                         for k in range(WINDOWS)], np.asarray(om_true))
    return out


def serve(name, cfg, streams, counter, mesh=None):
    """Serve every window through the async service; returns
    ({(stream, seq): omega}, the warm-up batch's result)."""
    import jax
    from repro.data import events as ev_data
    from repro.launch.serve import AsyncBatchedEstimationService, \
        _batch_class

    policy = ev_data.single_policy(BUCKET)
    svc = AsyncBatchedEstimationService(cfg, policy=policy,
                                        max_batch=MAX_BATCH, mesh=mesh)
    batch_b = _batch_class(STREAMS, MAX_BATCH, mesh)

    # set-up: compile (or load from the persistent cache) the one
    # executable class this traffic uses, and run it once
    c0 = counter.snapshot()
    t0 = time.perf_counter()
    first = [wins[0] for wins, _ in streams.values()]
    hints = [truth[0] for _, truth in streams.values()]
    ev, om, _ = svc.workload.make_batch(first, hints, BUCKET, batch_b)
    warm = jax.block_until_ready(
        svc.workload.executable(BUCKET, batch_b)(ev, om))
    setup_s = time.perf_counter() - t0
    c1 = counter.snapshot()

    for sid, (wins, truth) in streams.items():
        for k, w in enumerate(wins):
            svc.submit(sid, w, omega_hint=truth[0] if k == 0 else None)
    n_req = svc.pending()
    t0 = time.perf_counter()
    responses = svc.drain()
    serve_s = time.perf_counter() - t0
    c2 = counter.snapshot()

    spilled = svc.telemetry.registry.counter(
        "repro_serving_spilled_taps_total").value
    bad = [(r.stream_id, r.seq, r.status) for r in responses
           if r.status != "ok"]
    if len(responses) != n_req or bad:
        raise RuntimeError(f"{name}: {len(responses)}/{n_req} responses, "
                           f"not ok: {bad}")
    omegas = {(r.stream_id, r.seq): np.asarray(r.omega) for r in responses}
    errs = [np.linalg.norm(omegas[(sid, k)] - truth[k])
            for sid, (_, truth) in streams.items() for k in range(WINDOWS)]
    if not all(np.all(np.isfinite(o)) for o in omegas.values()):
        raise RuntimeError(f"{name}: non-finite estimate")
    log(f"[{name}] served {len(responses)}/{n_req} windows, all ok, in "
        f"{serve_s:.3f}s: {len(responses) / serve_s:.3f} windows/s "
        f"(batch class {batch_b}, bucket {BUCKET})")
    log(f"[{name}] batches={svc.stats['batches']} "
        f"service_compiles={svc.stats['compiles']} setup_s={setup_s:.3f} "
        f"xla_compiles_setup={c1[0] - c0[0]} "
        f"cache_hits_setup={c1[1] - c0[1]} "
        f"xla_compiles_serving={c2[0] - c1[0]} spilled_taps={spilled}")
    log(f"[{name}] rmse vs ground truth: "
        f"{float(np.sqrt(np.mean(np.square(errs)))):.6f} rad/s")
    if c2[0] != c1[0]:
        raise RuntimeError(f"{name}: compiled inside the serving window")
    if spilled:
        raise RuntimeError(f"{name}: {spilled} taps spilled to the slow "
                           f"path at capacity {cfg.engine_capacity}")
    return omegas, warm


def max_domega(a, b):
    return max(float(np.max(np.abs(a[key] - b[key]))) for key in a)


def batches_by_position(streams, omegas=None):
    """One (STREAMS, BUCKET) batch per window position, with the omega of
    each window (its served estimate, or the ground-truth warm start)."""
    import jax.numpy as jnp
    from repro.data import events as ev_data
    out = []
    for k in range(WINDOWS):
        wins = [w[k] for w, _ in streams.values()]
        om = [omegas[(sid, k)] if omegas else truth[k]
              for sid, (_, truth) in streams.items()]
        ev = ev_data.batch_windows(wins, BUCKET)
        out.append((ev, jnp.asarray(np.stack(om), jnp.float32)))
    return out


def check_kernel_compiled(cfg, streams):
    """The megakernel's compiled serving program holds the Pallas kernel
    (interpret mode would have lowered it to plain XLA ops)."""
    from repro.core.pipeline import estimate_batch_donated

    ev, om = batches_by_position(streams)[0]
    t0 = time.perf_counter()
    text = estimate_batch_donated.lower(ev, om, cfg).compile().as_text()
    n_calls = text.count("tpu_custom_call")
    log(f"[megakernel] compiled program: {n_calls} tpu_custom_call "
        f"occurrences ({time.perf_counter() - t0:.3f}s to lower+compile)")
    if not n_calls:
        raise RuntimeError("megakernel program holds no tpu_custom_call: "
                           "the kernel did not compile for the chip")


def stage_stats_fn(cfg):
    """jit'd fn(ev (B,N), omega (B,3), si) -> (megakernel BatchedEngineOut,
    reference-engine (B, 8) stats): one engine pass of stage `si` through
    both datapaths, on the tables `sort_events` builds at `omega`."""
    import functools

    import jax
    from repro.core.sorting import sort_events
    from repro.core.types import EventWindow
    from repro.kernels import batched_engine_stats
    from repro.kernels.ref import batched_engine_stats_ref

    @functools.partial(jax.jit, static_argnames=("si",))
    def fn(ev, om, si):
        st = cfg.stages[si]
        weights = jax.vmap(lambda x, y, t, p, v, o: sort_events(
            EventWindow(x, y, t, p, v), o, cfg.camera, st).weights)(
            ev.x, ev.y, ev.t, ev.p, ev.valid, om)
        mk = batched_engine_stats(
            ev, om, cfg.camera, st.scale, st.blur_taps, st.blur_sigma,
            weights=weights, rb=cfg.engine_rb, capacity=cfg.engine_capacity,
            dtype=cfg.dtype)
        ref = batched_engine_stats_ref(ev, om, cfg.camera, st.scale,
                                       st.blur_taps, st.blur_sigma, weights)
        return mk, ref

    return fn


def objective_error(mk, ref, n_pixels):
    """Largest error, over a batch, of the Eq. 12 objective the
    controller consumes, megakernel against reference engine: the
    variance relative to the reference's, and the gradient relative to
    the size of its G term, 2/P max_j |G_j| (the gradient itself is a
    difference that vanishes at the optimum)."""
    from repro.core.contrast import stats_to_objective
    mk, ref = np.asarray(mk, np.float64), np.asarray(ref, np.float64)
    v_mk, g_mk = stats_to_objective(mk, n_pixels)
    v_ref, g_ref = stats_to_objective(ref, n_pixels)
    g_scale = 2.0 / n_pixels * np.max(np.abs(ref[:, 2:5]), axis=1)
    err_v = np.abs(v_mk - v_ref) / np.abs(v_ref)
    err_g = np.max(np.abs(g_mk - g_ref), axis=1) / g_scale
    return float(max(np.max(err_v), np.max(err_g)))


def check_megakernel_stats(cfg, streams, omegas):
    """One engine pass of every stage, on every window, at the ground-truth
    and at the served omegas: the megakernel's objective must agree with
    the reference engine's within STATS_TOL, and no slab may spill at the
    configured capacity (a spilled window would be recomputed by the
    reference slow path, so the comparison would not see the kernel).
    The objective is compared through `objective_error`.
    Also reports whether one window's stats at B=1 equal its slot of B=4
    bit for bit."""
    import jax

    fn = stage_stats_fn(cfg)
    pixels = [int(np.prod(st.grid(cfg.camera))) for st in cfg.stages]
    for label, om_src in (("ground-truth", None), ("served", omegas)):
        worst, errs = 0, [0.0] * cfg.n_stages
        for ev, om in batches_by_position(streams, om_src):
            for si in range(cfg.n_stages):
                mk, ref = fn(ev, om, si)
                worst = max(worst, int(np.max(np.asarray(mk.spilled))))
                errs[si] = max(errs[si], objective_error(
                    mk.stats, ref, pixels[si]))
        log(f"[megakernel] at {label} omegas, every window: spill max "
            f"{worst} taps (capacity {cfg.engine_capacity}); objective vs "
            f"reference engine per stage: max_err="
            f"{[f'{e:.3e}' for e in errs]} (bound {STATS_TOL:.0e})")
        if worst:
            raise RuntimeError(f"megakernel spilled {worst} taps at "
                               f"capacity {cfg.engine_capacity}")
        if not max(errs) <= STATS_TOL:
            raise RuntimeError(f"megakernel objective differs from the "
                               f"reference engine by {max(errs)} > "
                               f"{STATS_TOL}")

    ev, om = batches_by_position(streams, omegas)[0]
    one = jax.tree.map(lambda a: a[:1], ev)
    equal, rel = [], []
    for si in range(cfg.n_stages):
        s4 = np.asarray(fn(ev, om, si)[0].stats)[0]
        s1 = np.asarray(fn(one, om[:1], si)[0].stats)[0]
        equal.append(bool(np.array_equal(s1, s4)))
        rel.append(float(np.max(np.abs(s1 - s4) / (np.abs(s4) + 1e-30))))
    log(f"[megakernel] stats of window s0/0 at B=1 vs slot 0 of B=4, per "
        f"stage: bitwise_equal={equal} max_rel_diff="
        f"{[f'{r:.3e}' for r in rel]}")


def check_sharded(warm, ndev):
    """The mesh run's outputs live on all `ndev` devices, one slot each."""
    shards = warm.omega.addressable_shards
    devices = {s.device for s in shards}
    log(f"[four chips] omega sharding: {warm.omega.sharding}; shards on "
        f"{sorted(d.id for d in devices)} with shapes "
        f"{sorted({tuple(s.data.shape) for s in shards})}")
    if len(devices) != ndev or len(warm.omega.sharding.device_set) != ndev:
        raise RuntimeError(f"outputs are not sharded over {ndev} devices")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip mesh phase (c)")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform!r} devices); "
              f"this check runs only on the chip", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, found {len(devices)}", file=sys.stderr)
        return 2
    log(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
        f"jax {jax.__version__}; compile cache {cache_dir}")

    from repro.configs import cmax_camel
    counter = CompileCounter()
    t0 = time.perf_counter()
    streams = make_streams()
    log(f"data: {STREAMS} streams x {WINDOWS} windows x "
        f"{cmax_camel.EVENTS_PER_WINDOW} events in "
        f"{time.perf_counter() - t0:.3f}s")

    if args.chips == 1:
        ref, _ = serve("reference", cmax_camel.CONFIG, streams, counter)
        mk, _ = serve("megakernel", cmax_camel.MEGAKERNEL, streams, counter)
        d = max_domega(mk, ref)
        log(f"[megakernel] max |domega| vs reference: {d:.6f} rad/s "
            f"(bound {DOMEGA_BOUND})")
        if not d <= DOMEGA_BOUND:
            raise RuntimeError(f"megakernel differs from the reference "
                               f"by {d} > {DOMEGA_BOUND} rad/s")
        check_kernel_compiled(cmax_camel.MEGAKERNEL, streams)
        check_megakernel_stats(cmax_camel.MEGAKERNEL, streams, mk)
    else:
        from repro.launch.mesh import make_mesh
        cfg = cmax_camel.MEGAKERNEL
        single, _ = serve("one chip", cfg, streams, counter)
        mesh = make_mesh((args.chips,), ("data",),
                         devices=devices[:args.chips])
        sharded, warm = serve("four chips", cfg, streams, counter,
                              mesh=mesh)
        check_sharded(warm, args.chips)
        d = max_domega(sharded, single)
        log(f"[four chips] max |domega| vs one chip: {d:.6f} rad/s "
            f"(bound {DOMEGA_BOUND})")
        if not d <= DOMEGA_BOUND:
            raise RuntimeError(f"sharded run differs from one chip by {d} "
                               f"> {DOMEGA_BOUND} rad/s")

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
