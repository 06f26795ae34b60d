"""Ragged-window batching layer + batched estimation service
(DESIGN.md §4): bucketing preserves every event, padded slots are inert,
and the batched/serving paths reproduce per-window estimation."""
import dataclasses

import jax
import numpy as np
import pytest
import jax.numpy as jnp

from helpers import random_window, small_camera

from repro.core import (CmaxConfig, StageConfig, estimate_batch,
                        estimate_sequence, estimate_streams, estimate_window)
from repro.core.pipeline import (estimate_batch_budgeted,
                                 estimate_window_budgeted)
from repro.core.types import EventWindow
from repro.data import events as ev_data
from repro.launch.serve import BatchedEstimationService


def fast_cfg(cam=None) -> CmaxConfig:
    """Two cheap stages on the tiny camera — adaptive logic intact."""
    return CmaxConfig(camera=cam or small_camera(), stages=(
        StageConfig(scale=0.5, tau=4e-4, max_iters=4, blur_taps=3,
                    blur_sigma=0.5, keep_ratio=0.5, step_scale=1.5),
        StageConfig(scale=1.0, tau=1.5e-4, max_iters=4, blur_taps=5,
                    blur_sigma=1.0, keep_ratio=1.0),
    ))


def ragged_streams(cam, n_streams=2, n_windows=3, n_max=512):
    """{stream: ([ragged windows], omega_true)} on the tiny camera."""
    out = {}
    for s in range(n_streams):
        spec = ev_data.SequenceSpec(
            name=f"s{s}", n_windows=n_windows, events_per_window=n_max,
            n_features=40, seed=50 + s, window_dt=0.03, camera=cam)
        wins, om_true, _ = ev_data.make_sequence(spec)
        lens = ev_data.ragged_lengths(n_windows, n_max // 3, n_max, seed=s)
        out[f"s{s}"] = (ev_data.ragged_from_sequence(wins, lens),
                        np.asarray(om_true))
    return out


# --- bucket policies -------------------------------------------------------


def test_pow2_policy_classes():
    pol = ev_data.pow2_policy(min_bucket=256, max_bucket=2048)
    assert pol.bucket_of(1) == 256
    assert pol.bucket_of(256) == 256
    assert pol.bucket_of(257) == 512
    assert pol.bucket_of(2048) == 2048
    with pytest.raises(ValueError):
        pol.bucket_of(2049)
    with pytest.raises(ValueError):
        pol.bucket_of(0)


def test_fixed_and_single_policies():
    pol = ev_data.fixed_policy([300, 100])
    assert pol.bucket_of(99) == 100
    assert pol.bucket_of(101) == 300
    with pytest.raises(ValueError):
        pol.bucket_of(301)
    single = ev_data.single_policy(1000)
    assert single.bucket_of(5) == 1000 == single.bucket_of(1000)


# --- padding / batching preserves events -----------------------------------


def test_pad_window_preserves_events():
    w = ragged_streams(small_camera())["s0"][0][0]
    padded = ev_data.pad_window(w, w.n + 37)
    assert padded.n == w.n + 37
    # every original event slot is intact, bit for bit
    for a, b in [(padded.x, w.x), (padded.y, w.y), (padded.t, w.t),
                 (padded.p, w.p), (padded.valid, w.valid)]:
        np.testing.assert_array_equal(np.asarray(a[:w.n]), np.asarray(b))
    # pad slots are invalid
    assert not np.asarray(padded.valid[w.n:]).any()
    assert int(padded.valid.sum()) == int(w.valid.sum())
    with pytest.raises(ValueError):
        ev_data.pad_window(w, w.n - 1)


def test_batch_windows_and_bucketize_preserve_events():
    cam = small_camera()
    wins = [w for ragged, _ in ragged_streams(cam, 3).values()
            for w in ragged]
    pol = ev_data.pow2_policy(min_bucket=128, max_bucket=512)
    buckets = ev_data.bucketize(wins, pol)
    # a partition: every window in exactly one bucket
    all_idx = sorted(i for idx in buckets.values() for i in idx)
    assert all_idx == list(range(len(wins)))
    for n_pad, idx in buckets.items():
        batch = ev_data.batch_windows([wins[i] for i in idx], n_pad)
        assert batch.x.shape == (len(idx), n_pad)
        for row, i in enumerate(idx):
            w = wins[i]
            assert pol.bucket_of(w.n) == n_pad
            np.testing.assert_array_equal(np.asarray(batch.x[row, :w.n]),
                                          np.asarray(w.x))
            np.testing.assert_array_equal(np.asarray(batch.valid[row, :w.n]),
                                          np.asarray(w.valid))
            assert not np.asarray(batch.valid[row, w.n:]).any()


def test_padding_overhead_ordering():
    cam = small_camera()
    wins = [w for ragged, _ in ragged_streams(cam, 3).values()
            for w in ragged]
    fine = ev_data.padding_overhead(wins, ev_data.pow2_policy(min_bucket=64))
    coarse = ev_data.padding_overhead(wins, ev_data.single_policy(512))
    assert 0.0 <= fine <= coarse < 1.0


def test_ragged_from_sequence_shapes():
    cam = small_camera()
    spec = ev_data.SequenceSpec(name="t", n_windows=3,
                                events_per_window=256, n_features=30,
                                seed=1, camera=cam)
    wins, _, _ = ev_data.make_sequence(spec)
    ragged = ev_data.ragged_from_sequence(wins, [256, 100, 17])
    assert [w.n for w in ragged] == [256, 100, 17]
    with pytest.raises(ValueError):
        ev_data.ragged_from_sequence(wins, [1, 2])
    with pytest.raises(ValueError):
        ev_data.ragged_from_sequence(wins, [1, 2, 600])


# --- batched estimation == per-window estimation ---------------------------


def test_estimate_batch_matches_per_window():
    cam = small_camera()
    cfg = fast_cfg(cam)
    wins = [w for ragged, _ in ragged_streams(cam, 2, 2).values()
            for w in ragged]
    n_pad = max(w.n for w in wins)
    batch = ev_data.batch_windows(wins, n_pad)
    om0 = jnp.zeros((len(wins), 3))
    res = estimate_batch(batch, om0, cfg)
    for i, w in enumerate(wins):
        ref = estimate_window(ev_data.pad_window(w, n_pad), jnp.zeros(3),
                              cfg)
        np.testing.assert_allclose(np.asarray(res.omega[i]),
                                   np.asarray(ref.omega), atol=1e-5)
        for tr_b, tr_1 in zip(res.stages, ref.stages):
            assert int(tr_b.iters[i]) == int(tr_1.iters)


def _assert_slot_equals(res, i, ref):
    """Every leaf of slot i of a batched result equals the lone window's
    result bit for bit (NaN padding of `v_history` included)."""
    for got, want in zip(jax.tree.leaves(res), jax.tree.leaves(ref)):
        np.testing.assert_array_equal(np.asarray(got[i]), np.asarray(want))


@pytest.mark.parametrize("entry,engine,b", [
    ("batch", "reference", 1), ("batch", "reference", 2),
    ("batch", "reference", 4), ("budgeted", "reference", 2),
    ("batch", "pallas", 2)])
def test_batch_slot_is_the_lone_window_bitwise(entry, engine, b):
    """On the engines that map over slots, slot i of a batch of any size
    runs the unbatched `estimate_window` program on window i alone, so it
    equals that call bit for bit, every trace leaf included: bitwise, not
    allclose, because no batch axis reaches the scatter, the sort or the
    CG-PR update."""
    cam = small_camera()
    cfg = dataclasses.replace(fast_cfg(cam), engine=engine)
    wins = [random_window(256, cam=cam, seed=80 + i) for i in range(b)]
    om0 = jnp.array([[0.1, -0.05, 0.2], [0.0, 0.3, -0.1],
                     [0.2, 0.0, 0.0], [-0.1, 0.1, 0.1]], jnp.float32)[:b]
    batch = ev_data.batch_windows(wins, 256)
    if entry == "batch":
        res = estimate_batch(batch, om0, cfg)
        refs = [estimate_window(w, om0[i], cfg) for i, w in enumerate(wins)]
    else:
        caps = jnp.array([[1, 4], [3, 2]], jnp.int32)[:b]
        res = estimate_batch_budgeted(batch, jnp.array(om0), caps, cfg)
        refs = [estimate_window_budgeted(w, om0[i], caps[i], cfg)
                for i, w in enumerate(wins)]
    for i, ref in enumerate(refs):
        _assert_slot_equals(res, i, ref)


@pytest.mark.parametrize("fill", ["leader", "others", "empty"])
def test_part_full_batch_real_slots_unchanged_by_fill(fill):
    """Two real windows in a batch of 4: whatever fills slots 2 and 3 (the
    service's leader replicas, other windows, or windows with no valid
    event), the real slots equal the two windows served alone."""
    cam = small_camera()
    cfg = fast_cfg(cam)
    real = [random_window(256, cam=cam, seed=90 + i) for i in range(2)]
    if fill == "leader":
        batch, n_fill = ev_data.fill_batch(real, 256, 4)
        assert n_fill == 2
    else:
        extra = [random_window(256, cam=cam, seed=95 + i,
                               valid_frac=0.0 if fill == "empty" else 1.0)
                 for i in range(2)]
        batch = ev_data.batch_windows(real + extra, 256)
    om0 = jnp.array([[0.1, -0.05, 0.2], [0.0, 0.3, -0.1],
                     [0.1, -0.05, 0.2], [0.1, -0.05, 0.2]], jnp.float32)
    res = estimate_batch(batch, om0, cfg)
    for i, w in enumerate(real):
        _assert_slot_equals(res, i, estimate_window(w, om0[i], cfg))


def test_estimate_streams_matches_sequence():
    cam = small_camera()
    cfg = fast_cfg(cam)
    spec = ev_data.SequenceSpec(name="t", n_windows=3,
                                events_per_window=256, n_features=40,
                                seed=9, window_dt=0.03, camera=cam)
    wins, _, _ = ev_data.make_sequence(spec)
    stack = EventWindow(*(jnp.stack([a, a]) for a in
                          (wins.x, wins.y, wins.t, wins.p, wins.valid)))
    oms, _ = estimate_streams(stack, jnp.zeros((2, 3)), cfg)
    ref, _ = estimate_sequence(wins, jnp.zeros(3), cfg)
    for s in range(2):
        np.testing.assert_allclose(np.asarray(oms[s]), np.asarray(ref),
                                   atol=1e-5)


# --- the serving loop ------------------------------------------------------


def test_service_matches_warm_started_reference():
    cam = small_camera()
    cfg = fast_cfg(cam)
    pol = ev_data.pow2_policy(min_bucket=128, max_bucket=512)
    svc = BatchedEstimationService(cfg, policy=pol, max_batch=4)
    streams = ragged_streams(cam, 3)
    for sid, (ragged, _) in streams.items():
        for w in ragged:
            svc.submit(sid, w)
    responses = svc.drain()
    assert len(responses) == sum(len(r) for r, _ in streams.values())
    by = {(r.stream_id, r.seq): r for r in responses}
    for sid, (ragged, _) in streams.items():
        om = np.zeros(3, np.float32)
        for k, w in enumerate(ragged):
            ref = estimate_window(
                ev_data.pad_window(w, pol.bucket_of(w.n)),
                jnp.asarray(om), cfg)
            om = np.asarray(ref.omega)
            np.testing.assert_allclose(by[(sid, k)].omega, om, atol=1e-5)


def test_service_preserves_per_stream_order_across_buckets():
    """A later window of a stream must never overtake an earlier one,
    even when the earlier one's length class keeps it out of the current
    batch (regression test for warm-start chain ordering)."""
    cam = small_camera()
    cfg = fast_cfg(cam)
    spec = ev_data.SequenceSpec(name="t", n_windows=2,
                                events_per_window=320, n_features=40,
                                seed=2, camera=cam)
    wins, _, _ = ev_data.make_sequence(spec)
    a = ev_data.ragged_from_sequence(wins, [300, 200])   # buckets 512, 256
    b = ev_data.ragged_from_sequence(wins, [200, 300])   # buckets 256, 512
    pol = ev_data.pow2_policy(min_bucket=256, max_bucket=512)
    svc = BatchedEstimationService(cfg, policy=pol, max_batch=2)
    for w in a:
        svc.submit("a", w)
    for w in b:
        svc.submit("b", w)
    seen = {"a": -1, "b": -1}
    while svc.pending():
        for r in svc.step():
            assert r.seq == seen[r.stream_id] + 1, (r.stream_id, r.seq)
            seen[r.stream_id] = r.seq
    assert seen == {"a": 1, "b": 1}


def test_service_executable_cache_bounded():
    cam = small_camera()
    cfg = fast_cfg(cam)
    pol = ev_data.pow2_policy(min_bucket=128, max_bucket=512)
    svc = BatchedEstimationService(cfg, policy=pol, max_batch=4)
    streams = ragged_streams(cam, 3)
    for sid, (ragged, _) in streams.items():
        for w in ragged:
            svc.submit(sid, w)
    svc.drain()
    first = svc.stats["compiles"]
    assert first == len({(r[0], r[1]) for r in svc._cache})
    # same shapes again -> zero new executables
    for sid, (ragged, _) in streams.items():
        for w in ragged:
            svc.submit(sid, w)
    svc.drain()
    assert svc.stats["compiles"] == first


def test_service_with_mesh():
    """mesh-backed service routes through estimate_batch_sharded and
    matches the per-window reference (1-device mesh in-process; the
    multi-device case is tests/test_sharding_subprocess.py)."""
    from repro.launch.mesh import make_mesh
    cam = small_camera()
    cfg = fast_cfg(cam)
    mesh = make_mesh((1, 1), ("data", "model"))
    pol = ev_data.pow2_policy(min_bucket=128, max_bucket=512)
    svc = BatchedEstimationService(cfg, policy=pol, max_batch=2, mesh=mesh)
    streams = ragged_streams(cam, 2, n_windows=2)
    for sid, (ragged, _) in streams.items():
        for w in ragged:
            svc.submit(sid, w)
    by = {(r.stream_id, r.seq): r for r in svc.drain()}
    for sid, (ragged, _) in streams.items():
        om = np.zeros(3, np.float32)
        for k, w in enumerate(ragged):
            ref = estimate_window(
                ev_data.pad_window(w, pol.bucket_of(w.n)),
                jnp.asarray(om), cfg)
            om = np.asarray(ref.omega)
            np.testing.assert_allclose(by[(sid, k)].omega, om, atol=1e-5)


def test_service_batch_fill_discarded():
    """3 requests in a batch class of 4: fill slot results never escape."""
    cam = small_camera()
    cfg = fast_cfg(cam)
    svc = BatchedEstimationService(
        cfg, policy=ev_data.single_policy(512), max_batch=4)
    streams = ragged_streams(cam, 3, n_windows=1)
    for sid, (ragged, _) in streams.items():
        svc.submit(sid, ragged[0])
    responses = svc.step()
    assert len(responses) == 3
    assert {r.batch_b for r in responses} == {4}
    assert svc.stats["fill_slots"] == 1
    assert svc.pending() == 0
