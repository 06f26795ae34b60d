"""Tests for the telemetry layer (DESIGN.md §6): metrics registry math,
span lifecycle through the real scheduler state machine (including
out-of-order harvest), deterministic FakeClock traces, disabled-mode
no-ops, the legacy `stats` compat view, the adaptation decision log, and
strict-budget refusal."""
import json

import numpy as np
import pytest

from helpers import small_camera

from repro.core.adaptive import residence_verdict
from repro.launch.serve import (BatchedEstimationService, FakeClock,
                                InlineExecutor, ManualExecutor, QosClass)
from repro.telemetry import (DECISION_FIELDS, SPAN_EVENTS, SPAN_FIELDS,
                             Histogram, MetricsRegistry, NullTracer,
                             Telemetry, read_jsonl, write_jsonl)

from test_serving_async import fast_cfg, make_svc, one_window


# ---------------------------------------------------------------------------
# registry: counters, labels, histogram boundary math, prometheus text
# ---------------------------------------------------------------------------


def test_counter_gauge_and_labels():
    reg = MetricsRegistry()
    c = reg.counter("repro_test_total", "help text")
    c.inc()
    c.inc(4)
    assert c.value == 5
    g = reg.gauge("repro_test_depth")
    g.set(7)
    assert g.value == 7
    fam = reg.counter("repro_test_shed_total", labels=("reason",))
    fam.labels(reason="deadline").inc(2)
    fam.labels(reason="budget").inc()
    snap = reg.snapshot()
    assert snap["repro_test_total"] == 5
    assert snap["repro_test_shed_total"] == {'reason="deadline"': 2,
                                             'reason="budget"': 1}
    with pytest.raises(ValueError):
        fam.labels(wrong="x")


def test_registry_idempotent_and_conflict():
    reg = MetricsRegistry()
    a = reg.counter("repro_test_total")
    b = reg.counter("repro_test_total")      # create-or-get: same child
    assert a is b
    with pytest.raises(ValueError):          # kind mismatch is an error
        reg.gauge("repro_test_total")
    with pytest.raises(ValueError):          # label mismatch too
        reg.counter("repro_test_total", labels=("x",))
    with pytest.raises(ValueError):
        reg.counter("bad name!")


def test_histogram_bucket_boundaries():
    """Prometheus `le` semantics: a value equal to a bound falls in that
    bound's bucket; cumulative counts are monotone and end at count."""
    h = Histogram(bounds=(1.0, 2.0, 5.0))
    for v in (0.5, 1.0, 1.5, 2.0, 2.0001, 5.0, 99.0):
        h.observe(v)
    assert h.counts == [2, 2, 2, 1]          # per-bucket, le-inclusive
    assert h.cumulative() == [2, 4, 6, 7]
    assert h.count == 7
    assert h.sum == pytest.approx(0.5 + 1.0 + 1.5 + 2.0 + 2.0001 + 5.0 + 99)
    with pytest.raises(ValueError):
        Histogram(bounds=(1.0, 1.0))         # not strictly increasing
    with pytest.raises(ValueError):
        Histogram(bounds=())


def test_histogram_quantile_interpolation():
    h = Histogram(bounds=(1.0, 2.0, 4.0))
    for v in [0.5] * 10:                     # all mass in the first bucket
        h.observe(v)
    assert h.quantile(0.5) == pytest.approx(0.5)   # linear within [0, 1]
    assert np.isnan(Histogram(bounds=(1.0,)).quantile(0.5))
    with pytest.raises(ValueError):
        h.quantile(1.5)


def test_prometheus_text_format():
    reg = MetricsRegistry()
    reg.counter("repro_test_total", "things").inc(3)
    fam = reg.counter("repro_test_shed_total", labels=("reason",))
    fam.labels(reason="deadline").inc()
    reg.histogram("repro_test_seconds", buckets=(0.1, 1.0)).observe(0.1)
    text = reg.to_prometheus()
    assert "# TYPE repro_test_total counter" in text
    assert "repro_test_total 3" in text
    assert 'repro_test_shed_total{reason="deadline"} 1' in text
    # le-inclusive: the 0.1 observation lands in the 0.1 bucket
    assert 'repro_test_seconds_bucket{le="0.1"} 1' in text
    assert 'repro_test_seconds_bucket{le="+Inf"} 1' in text
    assert "repro_test_seconds_count 1" in text


# ---------------------------------------------------------------------------
# spans through the real scheduler
# ---------------------------------------------------------------------------


def test_span_lifecycle_out_of_order_harvest():
    """Two batches dispatched, completed in REVERSE order: each span still
    carries its own submit->admit->dispatch->harvest ordering and its
    phases telescope exactly onto the response latency."""
    cam = small_camera()
    clock, ex = FakeClock(), ManualExecutor()
    tel = Telemetry(spans=True)
    svc = make_svc(cam, clock=clock, executor=ex, max_batch=1,
                   max_in_flight=2, telemetry=tel)
    svc.submit("a", one_window(cam, seed=0))
    clock.advance(0.25)
    svc.submit("b", one_window(cam, seed=1))
    svc.poll()                               # both dispatched (depth 2)
    h0, h1 = ex.in_flight()
    clock.advance(1.0)
    ex.release(h1)                           # newest batch finishes first
    done = svc.poll()
    clock.advance(0.5)
    ex.release(h0)
    done += svc.poll()
    rs = {r.stream_id: r for r in done}
    spans = {s.stream_id: s for s in tel.tracer.spans}
    assert set(spans) == {"a", "b"}
    # harvest order was b then a — span order follows completion
    assert [s.stream_id for s in tel.tracer.spans] == ["b", "a"]
    for sid in ("a", "b"):
        s, r = spans[sid], rs[sid]
        assert [e for e, _ in s.events] == ["submit", "admit", "dispatch",
                                            "harvest"]
        assert s.status == "ok" and s.iters == tuple(r.iters)
        assert s.latency_s == r.latency      # same clock reads, bit-equal
        assert sum(s.phases().values()) == pytest.approx(r.latency,
                                                         abs=1e-12)
    # both dispatched in the poll at t=0.25; a harvested at 1.75, b at 1.25
    assert spans["a"].phases()["execute"] == pytest.approx(1.5)
    assert spans["b"].phases()["execute"] == pytest.approx(1.0)
    assert spans["a"].phases()["queue_wait"] == pytest.approx(0.25)


def test_shed_span_and_reason_labels():
    cam = small_camera()
    clock, ex = FakeClock(), ManualExecutor()
    tel = Telemetry(spans=True)
    svc = make_svc(cam, clock=clock, executor=ex, max_batch=1,
                   max_in_flight=1, telemetry=tel)
    svc.submit("a", one_window(cam))                   # dispatches
    svc.poll()
    svc.submit("a", one_window(cam), deadline=clock.now() + 1.0)
    clock.advance(2.0)
    svc.poll()                                         # sheds seq 1
    shed = [s for s in tel.tracer.spans if s.status == "shed"]
    assert len(shed) == 1 and shed[0].seq == 1
    assert [e for e, _ in shed[0].events] == ["submit", "shed"]
    assert shed[0].phases() == {"queue_wait": pytest.approx(2.0)}
    snap = tel.registry.snapshot()
    assert snap["repro_serving_shed_total"]['reason="deadline"'] == 1
    assert svc.stats["shed"] == 1                      # compat view sums


def test_fakeclock_traces_are_deterministic():
    """Identical virtual-time runs produce bit-identical serialized
    traces — the determinism the DES benchmarks rely on."""
    cam = small_camera()

    def run():
        tel = Telemetry(spans=True, decisions=True)
        svc = make_svc(cam, clock=FakeClock(), executor=InlineExecutor(),
                       max_batch=2, telemetry=tel)
        for k in range(2):
            svc.submit("a", one_window(cam, seed=k))
            svc.submit("b", one_window(cam, seed=10 + k))
        svc.drain()
        return json.dumps(tel.trace_records(), sort_keys=True)

    # a span's `compile` says whether XLA compiled inside its dispatch,
    # which only a process's first run of these shapes does
    run()
    assert run() == run()


def test_disabled_mode_is_noop():
    cam = small_camera()
    svc = make_svc(cam, clock=FakeClock(), executor=InlineExecutor())
    assert isinstance(svc.telemetry.tracer, NullTracer)
    assert not svc.telemetry.enabled
    svc.submit("a", one_window(cam))
    svc.drain()
    assert svc.telemetry.tracer.spans == ()
    assert svc.telemetry.decisions.records == ()
    assert svc.telemetry.trace_records() == []
    assert svc.stats["windows"] == 1       # the registry is still on


# ---------------------------------------------------------------------------
# stats compat view
# ---------------------------------------------------------------------------


def test_stats_compat_view():
    cam = small_camera()
    svc = make_svc(cam, clock=FakeClock(), executor=InlineExecutor())
    assert sorted(svc.stats) == sorted(
        ["windows", "batches", "compiles", "event_slots", "raw_events",
         "fill_slots", "shed", "budgeted_windows", "budget_spent_uj"])
    svc.submit("a", one_window(cam))
    svc.drain()
    assert svc.stats["windows"] == 1 and svc.stats["batches"] == 1
    assert dict(svc.stats)["windows"] == 1            # Mapping protocol
    # writes route to the backing counters (the workload mutates these)
    svc.stats["budgeted_windows"] += 3
    assert svc.telemetry.registry.snapshot()[
        "repro_serving_budgeted_windows_total"] == 3
    with pytest.raises(TypeError):
        svc.stats["shed"] = 0                          # derived: read-only
    with pytest.raises(KeyError):
        svc.stats["nope"]
    # sync service: same backing, legacy key subset
    sync = BatchedEstimationService(fast_cfg(cam),
                                    policy=svc.policy, max_batch=2)
    assert sorted(sync.stats) == sorted(
        ["windows", "batches", "compiles", "event_slots", "raw_events",
         "fill_slots"])
    assert 0.0 <= sync.padded_slot_frac <= 1.0


# ---------------------------------------------------------------------------
# decision log + verdicts
# ---------------------------------------------------------------------------


def test_residence_verdicts():
    assert residence_verdict(0, None, 8) == "skip"
    assert residence_verdict(3, None, 8) == "run"
    assert residence_verdict(8, None, 8) == "max"
    assert residence_verdict(5, 5, 8) == "cap"
    assert residence_verdict(8, 12, 8) == "max"    # effective cap == max
    assert residence_verdict(4, 5, 8) == "run"
    assert residence_verdict(2, 2, None) == "cap"


def test_decision_log_reproduces_response_iters():
    """Every decision record's iters must rebuild the response's iters
    tuple exactly — with measured per-stage gains and sane verdicts."""
    cam = small_camera()
    tel = Telemetry(decisions=True)
    svc = make_svc(cam, clock=FakeClock(), executor=InlineExecutor(),
                   max_batch=2, telemetry=tel)
    for k in range(2):
        svc.submit("a", one_window(cam, seed=k))
        svc.submit("b", one_window(cam, seed=10 + k))
    rs = svc.drain()
    assert rs and all(r.status == "ok" for r in rs)
    logged = tel.decisions.iters_by_request()
    for r in rs:
        assert logged[(r.stream_id, r.seq)] == tuple(r.iters)
    n_stages = len(svc.cfg.stages)
    assert len(tel.decisions.records) == len(rs) * n_stages
    for rec in tel.decisions.records:
        assert tuple(rec) == DECISION_FIELDS
        assert 0 <= rec["batch"] < svc.stats["batches"]
        assert 0 <= rec["slot"] < 2
        assert rec["verdict"] in ("run", "cap", "max", "skip")
        assert rec["cap"] is None                 # unbudgeted run
        assert rec["max_iters"] == int(svc.cfg.stages[rec["stage"]].max_iters)
        assert np.isfinite(rec["gain"])


def test_decision_log_budget_caps():
    """Budgeted windows log the scheduler's cap; a stage that ran into it
    gets the 'cap' verdict."""
    cam = small_camera()
    tel = Telemetry(decisions=True)
    qos = [QosClass("tight", budget_uj=1e-3)]   # floor-only allocation
    svc = make_svc(cam, clock=FakeClock(), executor=InlineExecutor(),
                   max_batch=2, qos_classes=qos, telemetry=tel)
    svc.submit("a", one_window(cam, seed=0), qos="tight")
    svc.submit("b", one_window(cam, seed=1), qos="tight")
    rs = svc.drain()
    assert all(r.status == "ok" for r in rs)
    assert tel.decisions.records
    for rec in tel.decisions.records:
        assert rec["cap"] is not None
        assert rec["iters"] <= rec["cap"]
        if rec["iters"] == rec["cap"] and rec["cap"] < rec["max_iters"]:
            assert rec["verdict"] == "cap"
    logged = tel.decisions.iters_by_request()
    for r in rs:
        assert logged[(r.stream_id, r.seq)] == tuple(r.iters)


# ---------------------------------------------------------------------------
# strict budget refusal (satellite: shed accounting by reason)
# ---------------------------------------------------------------------------


def test_strict_budget_refuses_unaffordable_windows():
    """strict=True turns the budget into an admission test: a window whose
    modelled floor exceeds the budget is refused at submit with its own
    status and shed reason — while the default (non-strict) class still
    serves it at the floor (pinned by test_costmodel/test_conformance)."""
    cam = small_camera()
    tel = Telemetry(spans=True)
    qos = [QosClass("hard", budget_uj=1e-6, strict=True)]
    svc = make_svc(cam, clock=FakeClock(), executor=InlineExecutor(),
                   max_batch=2, qos_classes=qos, telemetry=tel)
    w = one_window(cam)
    seq = svc.submit("a", w, qos="hard")
    rs = svc.drain()
    assert [r.status for r in rs] == ["refused"]
    assert rs[0].seq == seq and rs[0].iters == ()
    snap = tel.registry.snapshot()
    assert snap["repro_serving_shed_total"]['reason="budget"'] == 1
    assert svc.stats["shed"] == 1
    span = tel.tracer.spans[0]
    assert span.status == "refused"
    assert [e for e, _ in span.events] == ["submit", "shed"]
    # an ample strict budget admits normally
    svc2 = make_svc(cam, clock=FakeClock(), executor=InlineExecutor(),
                    qos_classes=[QosClass("hard", budget_uj=1e9,
                                          strict=True)])
    svc2.submit("a", w, qos="hard")
    assert [r.status for r in svc2.drain()] == ["ok"]
    # a refused window skips the warm-start chain like a deadline shed
    assert svc.stats["windows"] == 0


def test_floor_cost_and_affordable():
    from repro.costmodel import BudgetScheduler, load_profile
    sched = BudgetScheduler(load_profile("paper_fpga_45nm"))
    plan = sched.plan_window(fast_cfg(), 512)
    uj, ms = sched.floor_cost(plan)
    assert uj > 0 and ms > 0
    # the floor is min_iters (=1) per stage of the plan's marginal costs
    assert uj == pytest.approx(sum(sp.cost_uj for sp in plan.stages))
    assert sched.affordable(plan, budget_uj=uj)          # exactly at floor
    assert not sched.affordable(plan, budget_uj=uj * 0.5)
    assert not sched.affordable(plan, budget_ms=ms * 0.5)
    assert sched.affordable(plan)                        # no budget: always


# ---------------------------------------------------------------------------
# exporters
# ---------------------------------------------------------------------------


def test_jsonl_roundtrip_and_summary(tmp_path):
    cam = small_camera()
    tel = Telemetry(spans=True, decisions=True)
    svc = make_svc(cam, clock=FakeClock(), executor=InlineExecutor(),
                   telemetry=tel)
    svc.submit("a", one_window(cam))
    svc.drain()
    trace = tmp_path / "trace.jsonl"
    n = tel.write_trace(str(trace))
    records = read_jsonl(str(trace))
    assert len(records) == n > 0
    span_recs = [r for r in records if r["type"] == "span"]
    assert span_recs and all(set(r) == set(SPAN_FIELDS)
                             for r in span_recs)
    dec_recs = [r for r in records if r["type"] == "decision"]
    assert dec_recs and all(set(r) == set(DECISION_FIELDS)
                            for r in dec_recs)
    metrics = tmp_path / "metrics.prom"
    tel.write_metrics(str(metrics))
    text = metrics.read_text()
    assert "repro_serving_windows_total 1" in text
    assert "# TYPE repro_serving_queue_wait_seconds histogram" in text
    summary = tel.summary()
    assert "spans: 1" in summary and "adaptation verdicts:" in summary
    # write_jsonl also accepts pre-serialized dicts
    write_jsonl(str(trace), records)
    assert read_jsonl(str(trace)) == records
