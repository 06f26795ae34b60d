"""Cross-workload conformance suite for the serving substrate.

The scheduler invariants of `AsyncBatchedEstimationService` are workload
CONTRACTS: any `repro.serving.Workload` plugin served through it must
uphold per-stream FIFO with carried state under arbitrary batch
completion order, bitwise slot independence at a fixed batch size,
deadline-shed semantics, QoS budget behavior, and executable-cache hit
accounting. This suite runs every contract against every shipped plugin
(`CmaxWorkload`, `LMDecodeWorkload`) through one parametrized harness —
a new workload is servable when its harness passes here.

The reference every schedule must reproduce is built from the workload's
OWN pieces at batch 1 (make_batch -> executable -> harvest, carried
state chained sequentially): bitwise equality of the batched service
against it is exactly the slot-independence the out-of-order refill
relies on.
"""
import numpy as np
import pytest

from helpers import small_camera

from repro.core import CmaxConfig, StageConfig
from repro.data import events as ev_data
from repro.data import lm as lm_data
from repro.launch.serve import (AsyncBatchedEstimationService, FakeClock,
                                InlineExecutor, ManualExecutor, QosClass)
from repro.serving import CmaxWorkload, LMDecodeWorkload
from repro.telemetry import SPAN_FIELDS, Telemetry


# ---------------------------------------------------------------------------
# harnesses: one per shipped workload
# ---------------------------------------------------------------------------


class CmaxHarness:
    """Contrast-maximization over ragged event windows; carried state is
    the warm-start omega."""

    name = "cmax"
    supports_budgets = True

    def __init__(self):
        self.cam = small_camera()
        self.cfg = CmaxConfig(camera=self.cam, stages=(
            StageConfig(scale=0.5, tau=4e-4, max_iters=4, blur_taps=3,
                        blur_sigma=0.5, keep_ratio=0.5, step_scale=1.5),
            StageConfig(scale=1.0, tau=1.5e-4, max_iters=4, blur_taps=5,
                        blur_sigma=1.0, keep_ratio=1.0),
        ))
        self.policy = ev_data.pow2_policy(min_bucket=128, max_bucket=512)
        self.workload = CmaxWorkload(self.cfg, policy=self.policy)

    def streams(self, n_streams=2, n_payloads=3, fixed=False):
        out = {}
        for s in range(n_streams):
            spec = ev_data.SequenceSpec(
                name=f"s{s}", n_windows=n_payloads, events_per_window=512,
                n_features=40, seed=50 + s, window_dt=0.03, camera=self.cam)
            wins, _, _ = ev_data.make_sequence(spec)
            lens = (np.full(n_payloads, 512) if fixed else
                    ev_data.ragged_lengths(n_payloads, 170, 512, seed=s))
            out[f"s{s}"] = ev_data.ragged_from_sequence(wins, lens)
        return out


class LMHarness:
    """LM decode in variable-length token chunks; carried state is the
    per-stream KV cache."""

    name = "lm_decode"
    supports_budgets = False

    def __init__(self):
        from repro.configs import get_smoke_config
        self.cfg = get_smoke_config("llama3.2-1b")
        self.policy = lm_data.chunk_policy(min_bucket=8, max_bucket=64)
        self.workload = LMDecodeWorkload(self.cfg, policy=self.policy,
                                         max_len=64)

    def streams(self, n_streams=2, n_payloads=3, fixed=False):
        if fixed:
            out = {}
            for s in range(n_streams):
                rng = np.random.default_rng(7 + s)
                out[f"lm{s}"] = [
                    lm_data.TokenChunk(rng.integers(
                        0, self.cfg.vocab_size, size=8).astype(np.int32))
                    for _ in range(n_payloads)]
            return out
        dcfg = lm_data.LMDataConfig(vocab_size=self.cfg.vocab_size,
                                    seq_len=16, global_batch=1, seed=0)
        return lm_data.token_streams(dcfg, n_streams, n_payloads, 5, 14)


@pytest.fixture(scope="module", params=["cmax", "lm"])
def harness(request):
    # module scope: the workload's compiled executables (and the LM
    # params) are shared across the suite; services are per-test
    return CmaxHarness() if request.param == "cmax" else LMHarness()


def reference_chain(wl, payloads):
    """Sequential batch-1 chain through the workload's own machinery —
    the ground truth every service schedule must reproduce bitwise."""
    state = wl.default_state()
    outs = []
    for p in payloads:
        b = wl.bucket_of(p)
        data, sb, _ = wl.make_batch([p], [state], b, 1)
        res = wl.executable(b, 1, donate=False)(data, sb)
        out, state, _, _ = wl.harvest(res, False)(0)
        outs.append(np.asarray(out))
    return outs


def make_svc(h, **kw):
    kw.setdefault("clock", FakeClock())
    return AsyncBatchedEstimationService(workload=h.workload, **kw)


# ---------------------------------------------------------------------------
# contract 1: per-stream FIFO with carried state, any completion order
# ---------------------------------------------------------------------------


def test_fifo_carried_state_any_completion_order(harness):
    """Streams' carried-state chains interleave across out-of-order batch
    completions (ManualExecutor releasing youngest/oldest alternately);
    every response still equals the sequential batch-1 chain bitwise, and
    each stream's responses come back in seq order."""
    streams = harness.streams(2, 3)
    ex = ManualExecutor()
    svc = make_svc(harness, executor=ex, max_batch=1, max_in_flight=2)
    for sid, ps in streams.items():
        for p in ps:
            svc.submit(sid, p)

    rs = []
    flip = False
    while svc.pending() or svc.in_flight():
        rs.extend(svc.poll())
        pending = ex.in_flight()
        if pending:                        # alternate which batch finishes
            ex.release(pending[-1] if flip else pending[0])
            flip = not flip
    rs.extend(svc.poll())

    assert len(rs) == 6 and all(r.status == "ok" for r in rs)
    by = {(r.stream_id, r.seq): r for r in rs}
    for sid, ps in streams.items():
        ref = reference_chain(harness.workload, ps)
        for k in range(len(ps)):
            np.testing.assert_array_equal(np.asarray(by[(sid, k)].omega),
                                          ref[k])
        seqs = [r.seq for r in rs if r.stream_id == sid]
        assert seqs == sorted(seqs)


# ---------------------------------------------------------------------------
# contract 2: bitwise slot independence at fixed batch size
# ---------------------------------------------------------------------------


def test_slot_independence_at_fixed_batch(harness):
    """Four same-bucket streams batched into one dispatch produce, per
    slot, exactly the bits of the batch-1 reference — the invariant that
    lets the service refill slots without cross-slot effects."""
    streams = harness.streams(4, 2, fixed=True)
    svc = make_svc(harness, executor=InlineExecutor(), max_batch=4)
    for sid, ps in streams.items():
        for p in ps:
            svc.submit(sid, p)
    rs = svc.drain()
    assert all(r.batch_b == 4 for r in rs)     # actually batched together
    by = {(r.stream_id, r.seq): r for r in rs}
    for sid, ps in streams.items():
        ref = reference_chain(harness.workload, ps)
        for k in range(len(ps)):
            np.testing.assert_array_equal(np.asarray(by[(sid, k)].omega),
                                          ref[k])


# ---------------------------------------------------------------------------
# contract 3: deadline shedding + carried-state chain skip
# ---------------------------------------------------------------------------


def test_deadline_shed_semantics_and_chain_skip(harness):
    """A queued request past its deadline is shed (batch_b=0, no iters,
    workload-defined placeholder output) and drops out of the stream's
    carried-state chain: the next window chains from the last COMPLETED
    result, as if the shed window was never submitted."""
    (_, ps), = harness.streams(1, 3).items()
    clock = FakeClock()
    svc = make_svc(harness, clock=clock, executor=InlineExecutor(),
                   max_batch=1)
    svc.submit("a", ps[0])
    rs = svc.drain()
    svc.submit("a", ps[1], deadline=clock.now() - 1.0)     # already late
    svc.submit("a", ps[2])
    rs += svc.drain()
    by = {r.seq: r for r in rs}
    assert by[1].status == "shed"
    assert by[1].batch_b == 0 and by[1].iters == ()
    assert svc.stats["shed"] == 1
    ref = reference_chain(harness.workload, [ps[0], ps[2]])  # skips ps[1]
    np.testing.assert_array_equal(np.asarray(by[0].omega), ref[0])
    np.testing.assert_array_equal(np.asarray(by[2].omega), ref[1])


def test_shed_before_first_completion_uses_default_placeholder(harness):
    """Shedding a stream's very first window returns the workload's
    placeholder for 'no state yet' — and never invents served output."""
    clock = FakeClock()
    svc = make_svc(harness, clock=clock, executor=InlineExecutor())
    (_, (p, *_)), = harness.streams(1, 1).items()
    svc.submit("fresh", p, deadline=clock.now() - 1.0)
    (r,) = svc.drain()
    assert r.status == "shed"
    expect = harness.workload.shed_output(None)
    np.testing.assert_array_equal(np.asarray(r.omega), np.asarray(expect))


# ---------------------------------------------------------------------------
# contract 4: QoS budget behavior
# ---------------------------------------------------------------------------


def test_qos_budget_behavior(harness):
    """Budget-supporting workloads: a tight budgeted class provably caps
    work (fewer total iterations than the unbudgeted drain of the same
    payloads) and the budget accounting is populated. Workloads without
    budget support must REFUSE budgeted classes at construction — a
    budget silently ignored would be an SLO violation."""
    qos = [QosClass("tight", budget_uj=1e-3)]
    if not harness.supports_budgets:
        with pytest.raises(ValueError, match="budget"):
            make_svc(harness, qos_classes=qos)
        return
    streams = harness.streams(2, 2)

    def total_iters(**kw):
        svc = make_svc(harness, executor=InlineExecutor(), max_batch=2,
                       **kw)
        for sid, ps in streams.items():
            for p in ps:
                svc.submit(sid, p, **({"qos": "tight"} if kw else {}))
        rs = svc.drain()
        return sum(sum(r.iters) for r in rs), svc.stats

    free_iters, _ = total_iters()
    tight_iters, stats = total_iters(qos_classes=qos)
    assert tight_iters < free_iters
    assert stats["budgeted_windows"] == 4
    assert stats["budget_spent_uj"] >= 0.0


# ---------------------------------------------------------------------------
# contract 5: executable-cache hit accounting
# ---------------------------------------------------------------------------


def test_executable_cache_hit_accounting(harness):
    """Every distinct (bucket, batch) pair compiles once; repeat shape
    classes are cache hits (no retrace), and the compile counter mirrors
    the cache exactly."""
    streams = harness.streams(3, 2)
    svc = make_svc(harness, executor=InlineExecutor(), max_batch=4)
    for sid, ps in streams.items():
        for p in ps:
            svc.submit(sid, p)
    svc.drain()
    first = svc.stats["compiles"]
    assert first == len(svc._cache) > 0
    batches0 = svc.stats["batches"]
    for sid, ps in streams.items():    # same shapes -> no new executables
        for p in ps:
            svc.submit(sid, p)
    svc.drain()
    assert svc.stats["compiles"] == first
    assert svc.stats["batches"] > batches0
    assert 0.0 <= svc.padded_slot_frac < 1.0


# ---------------------------------------------------------------------------
# contract 6: span schema — every workload emits the same telemetry shape
# ---------------------------------------------------------------------------


def test_span_schema_conformance(harness):
    """Spans are a WORKLOAD-AGNOSTIC contract: both plugins, served with
    tracing on, emit records with exactly the SPAN_FIELDS schema, the
    canonical ok-path event order, and iteration tuples and bucket/batch
    classes that mirror the responses bit-for-bit."""
    streams = harness.streams(2, 2)
    tel = Telemetry(spans=True)
    svc = make_svc(harness, executor=InlineExecutor(), max_batch=2,
                   telemetry=tel)
    for sid, ps in streams.items():
        for p in ps:
            svc.submit(sid, p)
    rs = svc.drain()
    spans = tel.tracer.spans
    assert len(spans) == len(rs) == 4
    by = {(r.stream_id, r.seq): r for r in rs}
    for s in spans:
        d = s.to_dict()
        assert tuple(d) == SPAN_FIELDS          # exact schema, exact order
        assert [e for e, _ in s.events] == ["submit", "admit", "dispatch",
                                            "harvest"]
        r = by[(s.stream_id, s.seq)]
        assert d["status"] == "ok" and d["qos"] == "standard"
        assert d["iters"] == list(r.iters)
        assert d["bucket_n"] == r.bucket_n and d["batch_b"] == r.batch_b
        assert isinstance(d["compile"], bool)
        assert d["latency_s"] == r.latency      # same clock reads
        assert sum(d["phases"].values()) == pytest.approx(r.latency,
                                                          abs=1e-12)
    # every span names the batch that carried it: one id per batch
    batch_ids = [s.batch for s in spans]
    assert all(isinstance(b, int) for b in batch_ids)
    assert len(set(batch_ids)) == svc.stats["batches"]
