"""Property tests for the batched megakernel (hypothesis; the conftest
shim runs a fixed number of seeded examples when hypothesis is absent).

Swept properties:

  * equivalence sweep — megakernel == vmapped-per-window fused kernels ==
    jnp reference across (n, scale, capacity, valid_frac, B) draws;
  * spill accounting — the spilled counter equals the independent numpy
    over-capacity count for arbitrary (capacity, rb) draws, and capacity
    large enough always yields spill 0;
  * warm-start chains — estimate_streams under engine="pallas_batched"
    preserves each stream's warm-start chain: a stream batched with
    others is bit-identical to the same stream estimated alone (fixed S).
"""
import numpy as np
import jax
import jax.numpy as jnp
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import CmaxConfig, EventWindow, StageConfig
from repro.core.geometry import warp_events
from repro.core.pipeline import estimate_streams, make_engine_pass
from repro.kernels import batched_engine_pass, batched_engine_stats
from repro.kernels.iwe_accum import CHUNK
from helpers import random_window, small_camera


def _stack(wins):
    return EventWindow(*[jnp.stack([getattr(w, f) for w in wins])
                         for f in ("x", "y", "t", "p", "valid")])


@settings(max_examples=8, deadline=None)
@given(n=st.integers(64, 320),
       scale=st.sampled_from([0.25, 0.5, 1.0]),
       capacity=st.sampled_from([1536, 2048]),
       valid_frac=st.floats(0.5, 1.0),
       b=st.integers(1, 3))
def test_megakernel_equivalence_sweep(n, scale, capacity, valid_frac, b):
    cam = small_camera()
    k = {0.25: 3, 0.5: 5, 1.0: 9}[scale]
    wins = [random_window(n, cam=cam, seed=100 + 7 * i + n,
                          valid_frac=valid_frac) for i in range(b)]
    batch = _stack(wins)
    rng = np.random.default_rng(n)
    om = jnp.asarray(rng.uniform(-1.5, 1.5, (b, 3)).astype(np.float32))
    weights = jnp.stack([jnp.where(w.valid, 1.0, 0.0) for w in wins])

    v_mk, g_mk, spilled = batched_engine_pass(
        batch, om, cam, scale, k, 1.0, weights=weights, capacity=capacity)
    assert int(jnp.sum(spilled)) == 0

    stage = StageConfig(scale=scale, tau=1e-3, max_iters=3, blur_taps=k,
                        blur_sigma=1.0, keep_ratio=scale)
    ref = jax.vmap(make_engine_pass(cam, stage, jnp.float32))
    v_ref, g_ref = ref(batch, weights, om)
    np.testing.assert_allclose(np.asarray(v_mk), np.asarray(v_ref),
                               rtol=2e-4, atol=1e-9)
    s = float(jnp.max(jnp.abs(g_ref))) + 1e-12
    np.testing.assert_allclose(np.asarray(g_mk) / s, np.asarray(g_ref) / s,
                               atol=2e-4)

    pal = jax.vmap(make_engine_pass(cam, stage, jnp.float32,
                                    engine="pallas", capacity=capacity))
    v_pal, g_pal = pal(batch, weights, om)
    np.testing.assert_allclose(np.asarray(v_mk), np.asarray(v_pal),
                               rtol=2e-4, atol=1e-9)


@settings(max_examples=8, deadline=None)
@given(n=st.integers(100, 640),
       capacity=st.sampled_from([128, 256, 512]),
       rb=st.sampled_from([4, 8]),
       seed=st.integers(0, 10_000))
def test_spill_accounting_matches_numpy(n, capacity, rb, seed):
    cam = small_camera()
    scale, k = 1.0, 9
    ev = random_window(n, cam=cam, seed=seed)
    rng = np.random.default_rng(seed)
    om = jnp.asarray(rng.uniform(-1.0, 1.0, (1, 3)).astype(np.float32))
    out = batched_engine_stats(_stack([ev]), om, cam, scale, k, 1.0,
                               rb=rb, capacity=capacity)
    Hs, _ = cam.grid(scale)
    n_slabs = -(-(Hs + k // 2) // rb)
    cap = -(-max(capacity, CHUNK) // CHUNK) * CHUNK
    w = warp_events(ev, om[0], cam, scale)
    contributing = np.asarray(w.in_range) & \
        (np.asarray(ev.p, np.float32) != 0.0)
    rows = np.concatenate([np.asarray(w.y0) + dy for dy in (0, 0, 1, 1)])
    live = np.concatenate([contributing] * 4)
    cnt = np.bincount(rows[live] // rb, minlength=n_slabs)[:n_slabs]
    assert int(out.spilled[0]) == int(np.maximum(cnt - cap, 0).sum())

    roomy = batched_engine_stats(_stack([ev]), om, cam, scale, k, 1.0,
                                 rb=rb, capacity=4 * n)
    assert int(roomy.spilled[0]) == 0


@settings(max_examples=4, deadline=None)
@given(seed=st.integers(0, 1000), k_windows=st.integers(2, 3))
def test_streams_warm_start_chain_preserved(seed, k_windows):
    """estimate_streams(pallas_batched): stream 0's chain, batched with a
    second stream, is bit-identical to the same chain with a different
    companion stream (fixed S=2 — slotwise independence of the lockstep)."""
    cam = small_camera()
    stages = (StageConfig(scale=0.5, tau=4e-4, max_iters=3, blur_taps=5,
                          blur_sigma=0.75, keep_ratio=0.5),
              StageConfig(scale=1.0, tau=1.5e-4, max_iters=3, blur_taps=9,
                          blur_sigma=1.0, keep_ratio=1.0),)
    cfg = CmaxConfig(camera=cam, stages=stages, engine="pallas_batched",
                     engine_capacity=1024)

    def stream(base):
        return [random_window(200, cam=cam, seed=base + i)
                for i in range(k_windows)]

    s0, s1, s2 = stream(seed), stream(seed + 40), stream(seed + 80)

    def run(streams):
        sw = EventWindow(*[
            jnp.stack([jnp.stack([getattr(w, f) for w in st_])
                       for st_ in streams])
            for f in ("x", "y", "t", "p", "valid")])
        om0 = jnp.zeros((len(streams), 3), jnp.float32)
        omegas, _ = estimate_streams(sw, om0, cfg)
        return omegas

    with_s1 = run([s0, s1])
    with_s2 = run([s0, s2])
    assert bool(jnp.all(with_s1[0] == with_s2[0]))
