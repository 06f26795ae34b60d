"""Batched megakernel (kernels/megakernel.py + the pallas_batched engine).

Contracts pinned here:

  * kernel equivalence — batched_engine_pass matches the vmapped reference
    engine pass (allclose: the one-hot MXU contraction sums in a different
    order than scatter-add, so bitwise equality vs the reference is not
    on the table);
  * batch invariance — one engine pass (binning prologue + megakernel) is
    slotwise deterministic: a window's (8,) stats are bit-identical whether
    it runs as B=1 or as any slot of a larger batch (interpreted here;
    chip_smoke.py reports the same on the chip);
  * fill invariance — at FIXED batch size (the serving layer buckets B),
    a slot's full pipeline result is bit-identical no matter what occupies
    the other slots (the invariant out-of-order refill relies on);
  * spill accounting — the spilled counter equals an independent numpy
    count of over-capacity contributing taps;
  * engine dispatch — CmaxConfig(engine="pallas_batched") threads through
    estimate_window / estimate_batch / estimate_batch_budgeted with
    results numerically equivalent to engine="reference".
"""
import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import CmaxConfig, EventWindow, StageConfig, estimate_batch, \
    estimate_window
from repro.core.geometry import warp_events
from repro.core.pipeline import estimate_batch_budgeted, make_engine_pass
from repro.core.types import ENGINES
from repro.kernels import batched_engine_pass, batched_engine_stats
from repro.kernels.iwe_accum import CHUNK
from helpers import random_window, small_camera

CAP = 1024


def _stack(wins):
    return EventWindow(*[jnp.stack([getattr(w, f) for w in wins])
                         for f in ("x", "y", "t", "p", "valid")])


def _tiny_cfg(cam, engine="pallas_batched"):
    stages = (
        StageConfig(scale=0.25, tau=1e-3, max_iters=3, blur_taps=3,
                    blur_sigma=0.5, keep_ratio=0.25, step_scale=2.0),
        StageConfig(scale=0.5, tau=4e-4, max_iters=3, blur_taps=5,
                    blur_sigma=0.75, keep_ratio=0.5, step_scale=1.4),
        StageConfig(scale=1.0, tau=1.5e-4, max_iters=3, blur_taps=9,
                    blur_sigma=1.0, keep_ratio=1.0, step_scale=1.0),
    )
    return CmaxConfig(camera=cam, stages=stages, engine=engine,
                      engine_capacity=CAP)


# ----------------------------------------------------------------------
# kernel-level equivalence + batch invariance
# ----------------------------------------------------------------------


@pytest.mark.parametrize("scale,k", [(0.25, 3), (0.5, 5), (1.0, 9)])
def test_megakernel_matches_reference_engine(scale, k):
    cam = small_camera()
    B, N = 3, 400
    wins = [random_window(N, cam=cam, seed=10 + i) for i in range(B)]
    batch = _stack(wins)
    om = jnp.array([[0.8, -0.4, 1.1], [0.0, 0.0, 0.0],
                    [-1.5, 2.0, 0.3]], jnp.float32)
    # the tiny camera has only 2 row slabs at s=0.25 — budget generously
    v_mk, g_mk, spilled = batched_engine_pass(
        batch, om, cam, scale, k, 0.5 + 0.25 * k / 3, capacity=2048)
    assert int(jnp.sum(spilled)) == 0

    stage = StageConfig(scale=scale, tau=1e-3, max_iters=3, blur_taps=k,
                        blur_sigma=0.5 + 0.25 * k / 3, keep_ratio=scale)
    ref = jax.vmap(make_engine_pass(cam, stage, jnp.float32))
    v_ref, g_ref = ref(batch, jnp.ones((B, N), jnp.float32), om)
    np.testing.assert_allclose(np.asarray(v_mk), np.asarray(v_ref),
                               rtol=1e-4)
    scale_g = float(jnp.max(jnp.abs(g_ref))) + 1e-12
    np.testing.assert_allclose(np.asarray(g_mk) / scale_g,
                               np.asarray(g_ref) / scale_g, atol=1e-4)


def test_megakernel_batch_invariance_bitwise():
    """One engine pass: stats of a window are bit-identical at B=1 and as
    any slot of a B=4 batch."""
    cam = small_camera()
    wins = [random_window(300, cam=cam, seed=20 + i, valid_frac=0.9)
            for i in range(4)]
    om = jnp.array([[0.5, -0.2, 0.9], [1.0, 0.0, -0.5],
                    [0.0, 1.2, 0.0], [-0.7, -0.7, 0.7]], jnp.float32)
    out_b = batched_engine_stats(_stack(wins), om, cam, 0.5, 5, 0.75,
                                 capacity=CAP)
    for i, w in enumerate(wins):
        out_1 = batched_engine_stats(_stack([w]), om[i:i + 1], cam, 0.5, 5,
                                     0.75, capacity=CAP)
        assert bool(jnp.all(out_1.stats[0] == out_b.stats[i]))
        assert int(out_1.spilled[0]) == int(out_b.spilled[i])


def test_megakernel_padded_and_dead_slots():
    """Padded (all-invalid) windows produce finite zero-ish stats and do
    not perturb live slots (bitwise, at fixed B)."""
    cam = small_camera()
    live = [random_window(256, cam=cam, seed=31 + i) for i in range(2)]
    dead = random_window(256, cam=cam, seed=33, valid_frac=0.0)
    om = jnp.array([[0.4, 0.1, -0.8], [1.0, -1.0, 0.5],
                    [0.2, 0.2, 0.2]], jnp.float32)
    w0 = jnp.where(dead.valid, 1.0, 0.0)  # mask, as sort_events would
    full = batched_engine_stats(
        _stack(live + [random_window(256, cam=cam, seed=99)]), om, cam,
        1.0, 9, 1.0, capacity=CAP)
    holey = batched_engine_stats(
        _stack(live + [dead]), om, cam, 1.0, 9, 1.0,
        weights=jnp.stack([jnp.ones((256,))] * 2 + [w0]),
        capacity=CAP)
    for i in range(2):
        assert bool(jnp.all(full.stats[i] == holey.stats[i]))
    assert bool(jnp.all(jnp.isfinite(holey.stats[2])))
    assert float(jnp.max(jnp.abs(holey.stats[2]))) == 0.0


def test_spill_counter_matches_numpy_accounting():
    cam = small_camera()
    rb, capacity = 8, CHUNK
    ev = random_window(600, cam=cam, seed=7)
    om = jnp.array([[0.3, -0.6, 1.4]], jnp.float32)
    scale, k = 1.0, 9
    out = batched_engine_stats(_stack([ev]), om, cam, scale, k, 1.0,
                               rb=rb, capacity=capacity)
    # independent numpy mirror of the slab-binning prologue
    Hs, _ = cam.grid(scale)
    n_slabs = -(-(Hs + k // 2) // rb)
    cap = capacity
    w = warp_events(ev, om[0], cam, scale)
    pw = np.asarray(ev.p, np.float32)     # weights=None -> all ones
    contributing = np.asarray(w.in_range) & (pw != 0.0)
    rows = np.concatenate([np.asarray(w.y0) + dy for dy in (0, 0, 1, 1)])
    live = np.concatenate([contributing] * 4)
    cnt = np.bincount(rows[live] // rb, minlength=n_slabs)[:n_slabs]
    expect = int(np.maximum(cnt - cap, 0).sum())
    assert int(out.spilled[0]) == expect
    assert expect > 0, "test should exercise a genuine spill"


def test_spilled_windows_take_exact_slow_path():
    """A window whose slabs overflow is recomputed by the reference
    datapath: at a capacity that spills, the pass still matches the
    reference engine, and a window that fits keeps the kernel's stats."""
    cam = small_camera()
    n = 1200
    batch = _stack([random_window(n, cam=cam, seed=80),
                    random_window(n, cam=cam, seed=81, valid_frac=0.1)])
    om = jnp.array([[0.3, -0.6, 1.4], [0.2, 0.1, -0.3]], jnp.float32)
    v_t, g_t, sp_t = batched_engine_pass(batch, om, cam, 1.0, 9, 1.0,
                                         capacity=CHUNK)
    v_r, g_r, sp_r = batched_engine_pass(batch, om, cam, 1.0, 9, 1.0,
                                         capacity=4 * n)
    assert int(sp_t[0]) > 0 and int(sp_t[1]) == 0
    assert int(jnp.sum(sp_r)) == 0
    stage = StageConfig(scale=1.0, tau=1e-3, max_iters=3, blur_taps=9,
                        blur_sigma=1.0, keep_ratio=1.0)
    ref = jax.vmap(make_engine_pass(cam, stage, jnp.float32))
    v_ref, g_ref = ref(batch, jnp.ones((2, n), jnp.float32), om)
    np.testing.assert_allclose(np.asarray(v_t), np.asarray(v_ref),
                               rtol=1e-4)
    s = float(jnp.max(jnp.abs(g_ref))) + 1e-12
    np.testing.assert_allclose(np.asarray(g_t) / s, np.asarray(g_ref) / s,
                               atol=1e-4)
    # the window that fits is untouched by its neighbour's slow path
    assert float(v_t[1]) == float(v_r[1])
    assert bool(jnp.all(g_t[1] == g_r[1]))


def test_service_counts_spilled_taps():
    """Served windows that overflow a slab stay exact and are counted in
    the service's repro_serving_spilled_taps_total."""
    from repro.data import events as ev_data
    from repro.launch.serve import AsyncBatchedEstimationService
    cam = small_camera()
    n = 1024
    wins = [random_window(n, cam=cam, seed=90 + i) for i in range(2)]
    cfg = dataclasses.replace(_tiny_cfg(cam), engine_capacity=CHUNK)
    svc = AsyncBatchedEstimationService(
        cfg, policy=ev_data.single_policy(n), max_batch=2)
    for i, w in enumerate(wins):
        svc.submit(f"s{i}", w)
    responses = svc.drain()
    assert [r.status for r in responses] == ["ok", "ok"]
    counter = svc.telemetry.registry.counter(
        "repro_serving_spilled_taps_total")
    assert counter.value > 0
    ref = estimate_batch(_stack(wins), jnp.zeros((2, 3), jnp.float32),
                         _tiny_cfg(cam, "reference"))
    for r in responses:
        np.testing.assert_allclose(
            np.asarray(r.omega), np.asarray(ref.omega[int(r.stream_id[1:])]),
            atol=5e-4)


# ----------------------------------------------------------------------
# pipeline-level dispatch
# ----------------------------------------------------------------------


def test_engine_validation():
    assert "pallas_batched" in ENGINES
    with pytest.raises(ValueError):
        CmaxConfig(engine="nope")


def test_estimate_batch_matches_reference_engine():
    cam = small_camera()
    B = 3
    wins = [random_window(256, cam=cam, seed=40 + i) for i in range(B)]
    batch = _stack(wins)
    om0 = jnp.tile(jnp.array([[0.1, -0.05, 0.2]], jnp.float32), (B, 1))
    res_ref = estimate_batch(batch, om0, _tiny_cfg(cam, "reference"))
    res_mk = estimate_batch(batch, om0, _tiny_cfg(cam, "pallas_batched"))
    np.testing.assert_allclose(np.asarray(res_mk.omega),
                               np.asarray(res_ref.omega), atol=5e-4)
    for tr_r, tr_m in zip(res_ref.stages, res_mk.stages):
        assert tr_m.iters.shape == tr_r.iters.shape
        np.testing.assert_allclose(np.asarray(tr_m.v_final),
                                   np.asarray(tr_r.v_final), rtol=1e-3)


def test_estimate_batch_fill_invariance_bitwise():
    """At fixed B, a slot's result is bit-identical regardless of what
    occupies the other slots — the serving refill invariant, now through
    the megakernel lockstep path."""
    cam = small_camera()
    cfg = _tiny_cfg(cam)
    w_a = random_window(256, cam=cam, seed=50)
    w_b = random_window(256, cam=cam, seed=51)
    w_c = random_window(256, cam=cam, seed=52)
    om0 = jnp.tile(jnp.array([[0.1, -0.05, 0.2]], jnp.float32), (3, 1))
    r1 = estimate_batch(_stack([w_a, w_b, w_c]), om0, cfg)
    r2 = estimate_batch(_stack([w_c, w_b, w_a]), om0, cfg)
    assert bool(jnp.all(r1.omega[1] == r2.omega[1]))
    for tr1, tr2 in zip(r1.stages, r2.stages):
        assert bool(jnp.all(tr1.v_history[1] == tr2.v_history[1]))
        assert int(tr1.iters[1]) == int(tr2.iters[1])


def test_estimate_window_close_to_batch_slot():
    """B=1 vs slot-of-B agree numerically. The engine pass is bitwise
    batch-invariant, but the vmapped pipeline around it (sorting, CG-PR
    updates) compiles differently per batch shape and can differ in the
    last bit, so across batch sizes the contract is allclose."""
    cam = small_camera()
    cfg = _tiny_cfg(cam)
    wins = [random_window(256, cam=cam, seed=60 + i) for i in range(3)]
    om0 = jnp.tile(jnp.array([[0.1, -0.05, 0.2]], jnp.float32), (3, 1))
    rb = estimate_batch(_stack(wins), om0, cfg)
    rw = estimate_window(wins[1], om0[1], cfg)
    np.testing.assert_allclose(np.asarray(rw.omega),
                               np.asarray(rb.omega[1]), atol=1e-4)


def test_estimate_batch_budgeted_caps_respected():
    cam = small_camera()
    cfg = _tiny_cfg(cam)
    B = 2
    wins = [random_window(256, cam=cam, seed=70 + i) for i in range(B)]
    om0 = np.zeros((B, 3), np.float32)   # omega0s is donated: fresh per call
    caps = jnp.array([[1, 2, 1], [3, 3, 3]], jnp.int32)
    res = estimate_batch_budgeted(_stack(wins), jnp.array(om0), caps, cfg)
    iters = np.stack([np.asarray(tr.iters) for tr in res.stages], axis=1)
    assert (iters <= np.asarray(caps)).all()
    # caps >= max_iters reproduce the unbudgeted path exactly
    res_full = estimate_batch_budgeted(
        _stack(wins), jnp.array(om0), jnp.full((B, 3), 99, jnp.int32), cfg)
    res_plain = estimate_batch(_stack(wins), jnp.array(om0), cfg)
    assert bool(jnp.all(res_full.omega == res_plain.omega))
