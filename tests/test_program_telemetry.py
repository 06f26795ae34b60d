"""The program's own names for a profile (DESIGN.md §6): the service's
`serve.*` regions with their batch ids, the `cmax.*` device scopes of the
compiled pipeline, the engine-pass counters, and the `compile` flag that
counts XLA compiles inside a dispatch."""
import dataclasses
import glob
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from helpers import random_window, small_camera

from repro.core import CmaxConfig, EventWindow, StageConfig
from repro.core.pipeline import estimate_batch
from repro.data import events as ev_data
from repro.launch.serve import (AsyncBatchedEstimationService,
                                BatchedEstimationService, FakeClock,
                                InlineExecutor, _count_passes,
                                _ServingMetrics)
from repro.serving.workload import CmaxWorkload
from repro.telemetry import MetricsRegistry, NullTracer, Telemetry, Tracer

from test_serving_async import fast_cfg, make_svc, one_window


def test_null_tracer_region_is_a_shared_noop():
    a, b = NullTracer(), NullTracer()
    ctx = a.region("serve.poll")
    assert ctx is a.region("serve.launch", batch=3) is b.region("x")
    with ctx as got:
        assert got is None
    assert a.spans == ()


def test_live_tracer_region_is_a_profiler_annotation():
    ctx = Tracer().region("serve.dispatch", batch=7)
    assert isinstance(ctx, jax.profiler.TraceAnnotation)


def _host_regions(trace_dir):
    from jax.profiler import ProfileData
    path, = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("serve."):
                        out.append((e.start_ns, e.name, dict(e.stats)))
    return sorted(out)


def test_profiler_regions_carry_the_batch_ids_of_spans_and_decisions(
        tmp_path):
    """Under the profiler, a traced service writes one `serve.launch`,
    `serve.make_batch`, `serve.dispatch` and `serve.harvest` region per
    batch, whose `batch` stats are the ids its request spans and decision
    records carry."""
    cam = small_camera()
    tel = Telemetry(spans=True, decisions=True)
    svc = make_svc(cam, executor=InlineExecutor(), max_batch=2,
                   telemetry=tel)
    for k in range(2):
        svc.submit("a", one_window(cam, seed=k))
        svc.submit("b", one_window(cam, seed=10 + k))
    svc.submit("c", one_window(cam, seed=20))
    with jax.profiler.trace(str(tmp_path)):
        rs = svc.drain()
    assert len(rs) == 5
    regions = _host_regions(tmp_path)
    names = {n for _, n, _ in regions}
    assert {"serve.poll", "serve.launch", "serve.make_batch",
            "serve.dispatch", "serve.harvest"} <= names
    by_name = {}
    for _, n, st in regions:
        if n != "serve.poll":
            by_name.setdefault(n, []).append(int(st["batch"]))
    launched = by_name["serve.launch"]
    assert launched == sorted(launched) == list(range(len(launched)))
    for n in ("serve.make_batch", "serve.dispatch", "serve.harvest"):
        assert sorted(by_name[n]) == launched

    span_batch = {(s.stream_id, s.seq): s.batch for s in tel.tracer.spans}
    assert sorted(set(span_batch.values())) == launched
    for d in tel.decisions.records:
        assert d["batch"] == span_batch[(d["stream_id"], d["seq"])]
    # a batch's slots are the positions of its windows, each given once
    slots = {}
    for d in tel.decisions.records:
        slots.setdefault(d["batch"], set()).add((d["stream_id"], d["slot"]))
    for b, got in slots.items():
        members = sorted(k for k, v in span_batch.items() if v == b)
        assert sorted(s for _, s in got) == list(range(len(members)))


@pytest.mark.parametrize("engine,caps,slot_passes", [
    # each window its own passes (8 + 8), each fill slot the leader's (8)
    ("reference", None, 16 + 2 * 8),
    # budgeted fill slots are capped at 1 iteration: the leader's
    # (2, 3, 0) cut to (1, 1, 0), so 2 + 2 + 1 passes each
    ("reference", [[9, 9, 9], [9, 9, 9], [1, 1, 1], [1, 1, 1]],
     16 + 2 * 5),
    # lockstep: every slot the slowest per stage, 5 + 4 + 1 -> 4 slots x 10
    ("pallas_batched", None, 40)])
def test_pass_counters_on_a_hand_computed_batch(engine, caps, slot_passes):
    """Two real windows in a batch of 4; the windows needed their own
    passes per stage (iters + 1). What the device ran depends on whether
    the engine runs the batch in lockstep."""
    m = _ServingMetrics(MetricsRegistry())
    wl = CmaxWorkload(dataclasses.replace(fast_cfg(), engine=engine))
    assert wl.lockstep == (engine == "pallas_batched")
    _count_passes(m, wl, [(2, 3, 0), (4, 1, 0)], batch_b=4,
                  caps=None if caps is None else np.asarray(caps))
    assert m.slot_passes.value == slot_passes
    assert m.window_passes.value == (3 + 4 + 1) + (5 + 2 + 1)


@pytest.mark.parametrize("engine", ["reference", "pallas_batched"])
def test_pass_counters_of_a_served_run(engine):
    """A batch of 3 windows in class 4 and one of 2 in class 2. On the
    reference engine the device ran each window's passes plus the
    leader's for the fill slot; on the megakernel every slot ran the
    batch's slowest window's passes per stage."""
    cam = small_camera()
    tel = Telemetry(decisions=True)
    svc = AsyncBatchedEstimationService(
        dataclasses.replace(fast_cfg(cam), engine=engine),
        policy=ev_data.pow2_policy(min_bucket=128, max_bucket=512),
        clock=FakeClock(), executor=InlineExecutor(), max_batch=4,
        telemetry=tel)
    for k in range(2):
        svc.submit("a", one_window(cam, seed=k))
        svc.submit("b", one_window(cam, seed=10 + k))
    svc.submit("c", one_window(cam, seed=20))
    rs = svc.drain()
    snap = tel.registry.snapshot()
    assert snap["repro_serving_window_passes_total"] == sum(
        sum(it + 1 for it in r.iters) for r in rs)
    batch_b = {(r.stream_id, r.seq): r.batch_b for r in rs}
    passes = {}     # batch -> {slot: passes per stage}
    width = {}      # batch -> batch class
    for d in tel.decisions.records:
        slots = passes.setdefault(d["batch"], {})
        slots.setdefault(d["slot"], []).append(d["iters"] + 1)
        width[d["batch"]] = batch_b[(d["stream_id"], d["seq"])]
    assert sorted((len(s), width[b]) for b, s in passes.items()) == \
        [(2, 2), (3, 4)]
    want = 0
    for b, slots in passes.items():
        p = np.asarray([slots[i] for i in range(len(slots))])
        if engine == "pallas_batched":
            want += width[b] * int(p.max(axis=0).sum())
        else:
            want += int(p.sum()) + (width[b] - len(p)) * int(p[0].sum())
    assert snap["repro_serving_slot_passes_total"] == want
    assert want >= snap["repro_serving_window_passes_total"]


@pytest.mark.parametrize("sync", [False, True])
def test_batch_class_one_runs_no_pass_in_lockstep(sync):
    cam = small_camera()
    if sync:
        svc = BatchedEstimationService(
            fast_cfg(cam), policy=ev_data.pow2_policy(min_bucket=128,
                                                      max_bucket=512),
            max_batch=1)
    else:
        svc = make_svc(cam, executor=InlineExecutor(), max_batch=1)
    for k in range(3):
        svc.submit("a", one_window(cam, seed=k))
    svc.drain()
    snap = svc.telemetry.registry.snapshot()
    assert snap["repro_serving_slot_passes_total"] > 0
    assert snap["repro_serving_slot_passes_total"] == \
        snap["repro_serving_window_passes_total"]


def test_compile_flag_counts_xla_compiles_inside_the_dispatch():
    """A dispatch that compiles says so; the same shape class served by a
    second service comes from JAX's cache: an executable-cache miss of
    that service, but no XLA compile and no `compile` flag."""
    cam = small_camera()
    # a length class no other test serves, so the first run compiles
    policy = ev_data.single_policy(384)

    def serve():
        tel = Telemetry(spans=True)
        svc = make_svc(cam, executor=InlineExecutor(), max_batch=1,
                       policy=policy, telemetry=tel)
        svc.submit("a", one_window(cam, seed=3))
        svc.drain()
        return tel.tracer.spans[0], tel.registry.snapshot()

    first, snap1 = serve()
    again, snap2 = serve()
    assert first.compile is True
    assert snap1["repro_serving_xla_compiles_total"] >= 1
    assert again.compile is False
    assert snap2["repro_serving_xla_compiles_total"] == 0
    assert snap1["repro_serving_compiles_total"] == \
        snap2["repro_serving_compiles_total"] == 1


def _stages():
    return (
        StageConfig(scale=0.25, tau=1e-3, max_iters=3, blur_taps=3,
                    blur_sigma=0.5, keep_ratio=0.25, step_scale=2.0),
        StageConfig(scale=0.5, tau=4e-4, max_iters=3, blur_taps=5,
                    blur_sigma=0.75, keep_ratio=0.5, step_scale=1.4),
        StageConfig(scale=1.0, tau=1.5e-4, max_iters=3, blur_taps=9,
                    blur_sigma=1.0, keep_ratio=1.0, step_scale=1.0),
    )


@pytest.mark.parametrize("engine,scopes", [
    ("reference", ()),
    ("pallas_batched", ("cmax.bin_taps", "cmax.megakernel",
                        "cmax.spill_slow_path")),
])
def test_compiled_pipeline_carries_the_device_scopes(engine, scopes):
    """The compiled batch function's HLO names every stage and, inside
    each, the stage-entry sort, the engine pass and the update."""
    cam = small_camera()
    cfg = CmaxConfig(camera=cam, stages=_stages(), engine=engine,
                     engine_capacity=1024)
    wins = [random_window(256, cam=cam, seed=i) for i in range(2)]
    ev = EventWindow(*[jnp.stack([getattr(w, f) for w in wins])
                       for f in ("x", "y", "t", "p", "valid")])
    text = estimate_batch.lower(ev, jnp.zeros((2, 3)), cfg).compile() \
        .as_text()
    op_names = re.findall(r'op_name="([^"]*)"', text)
    chains = {"/".join(p for p in n.split("/") if p.startswith("cmax."))
              for n in op_names}
    for k in range(3):
        for inner in ("cmax.sort", "cmax.engine_pass", "cmax.update"):
            assert f"cmax.stage{k}/{inner}" in chains or any(
                c.startswith(f"cmax.stage{k}/{inner}/") for c in chains)
    for inner in scopes:
        assert any(c.startswith("cmax.stage") and
                   c.split("/")[1:3] == ["cmax.engine_pass", inner]
                   for c in chains), inner
