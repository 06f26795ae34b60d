"""Cost-model subsystem: profile loading/validation, paper-ratio
reproduction, scheduler properties, and the budgeted serving path."""
import dataclasses

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro import costmodel
from repro.costmodel import (Allocation, BudgetScheduler, HwParams,
                             MissingSectionError, ProfileError, StagePlan,
                             UnknownKeyError, WindowPlan, account_stage,
                             account_window, available_profiles,
                             load_profile, paper_trace, read_profile_dict)
from repro.costmodel.model import Account
from repro.costmodel.profiles import SCHEMA, validate

PAPER = "paper_fpga_45nm"


# ---------------------------------------------------------------------------
# profile round-trip + validation
# ---------------------------------------------------------------------------


def _sections():
    """A complete, valid profile as nested dicts (the paper table)."""
    return {sec: dict(body) for sec, body in
            read_profile_dict(PAPER).items()}


def _write_csv(path, sections):
    lines = []
    for sec, body in sections.items():
        lines.append(f"# {sec}")
        for k, v in body.items():
            lines.append(f"{k},{v}")
    path.write_text("\n".join(lines) + "\n")


def _write_toml(path, sections):
    lines = []
    for sec, body in sections.items():
        lines.append(f"[{sec}]")
        for k, v in body.items():
            if isinstance(v, str):
                lines.append(f'{k} = "{v}"')
            else:
                lines.append(f"{k} = {v}")
    path.write_text("\n".join(lines) + "\n")


def test_csv_roundtrip(tmp_path):
    p = tmp_path / "rt.csv"
    _write_csv(p, _sections())
    assert read_profile_dict(str(p)) == _sections()


def test_toml_roundtrip(tmp_path):
    p = tmp_path / "rt.toml"
    _write_toml(p, _sections())
    assert read_profile_dict(str(p)) == _sections()


def test_csv_meta_values_may_contain_commas(tmp_path):
    secs = _sections()
    secs["meta"]["description"] = "45 nm, 200 MHz, calibrated"
    p = tmp_path / "meta.csv"
    _write_csv(p, secs)
    got = read_profile_dict(str(p))
    assert got["meta"]["description"] == "45 nm, 200 MHz, calibrated"


def test_unknown_key_raises(tmp_path):
    secs = _sections()
    secs["pipeline"]["freq_mhz"] = 200.0    # typo'd key
    p = tmp_path / "typo.csv"
    _write_csv(p, secs)
    with pytest.raises(UnknownKeyError, match="freq_mhz"):
        read_profile_dict(str(p))


def test_unknown_section_raises():
    secs = _sections()
    secs["pipelines"] = {"freq_hz": 1.0}
    with pytest.raises(UnknownKeyError, match="pipelines"):
        validate(secs)


def test_missing_section_raises():
    secs = _sections()
    del secs["logic"]
    with pytest.raises(MissingSectionError, match="logic"):
        validate(secs)


def test_missing_key_raises(tmp_path):
    secs = _sections()
    del secs["memory.iwe"]["e_read_pj"]
    with pytest.raises(MissingSectionError, match="e_read_pj"):
        validate(secs)


def test_wrong_type_and_nonpositive_rejected():
    secs = _sections()
    secs["pipeline"]["vote_taps"] = True
    with pytest.raises(ProfileError):
        validate(secs)
    secs = _sections()
    secs["pipeline"]["freq_hz"] = 0.0
    with pytest.raises(ProfileError, match="freq_hz"):
        validate(secs)


def test_unknown_profile_name_lists_shipped():
    with pytest.raises(ProfileError, match=PAPER):
        read_profile_dict("no_such_chip")


def test_all_shipped_profiles_load():
    names = available_profiles()
    assert PAPER in names and "cpu_interpret" in names \
        and "tpu_v4_estimate" in names
    for name in names:
        hw = load_profile(name)
        assert hw.freq_hz > 0 and hw.vote_taps > 0 and hw.channels > 0
        assert hw.iwe.e_read_pj > 0 and hw.line.e_write_pj > 0


# ---------------------------------------------------------------------------
# shim: core.energy is a thin face over costmodel
# ---------------------------------------------------------------------------


def test_legacy_hwparams_is_paper_profile():
    from repro.core import energy
    assert energy.HwParams() == load_profile(PAPER)
    assert energy.HwParams is costmodel.HwParams
    assert energy.account_stage is costmodel.account_stage
    assert energy.account_window is costmodel.account_window


# ---------------------------------------------------------------------------
# accounting semantics (the satellite fixes)
# ---------------------------------------------------------------------------


def _stage_kwargs(**over):
    kw = dict(camel=True, passes=1.0, n_ret=1000.0, n_total=4000.0,
              P=600.0, taps=3, merge_reduction=0.5, sort_this_stage=False)
    kw.update(over)
    return kw


def test_fractional_passes_linear():
    hw = load_profile(PAPER)
    one, frac = Account(), Account()
    account_stage(one, hw, **_stage_kwargs(passes=1.0))
    account_stage(frac, hw, **_stage_kwargs(passes=2.5))
    assert frac.total_accesses == pytest.approx(2.5 * one.total_accesses)
    assert frac.cycles == pytest.approx(2.5 * one.cycles)


def test_taps_parameter_drives_line_buffer_reads():
    hw = load_profile(PAPER)
    a3, a9 = Account(), Account()
    account_stage(a3, hw, **_stage_kwargs(taps=3))
    account_stage(a9, hw, **_stage_kwargs(taps=9))
    C, P = hw.channels, 600.0
    assert a9.line_r - a3.line_r == pytest.approx(C * P * 6)
    assert a9.line_w == a3.line_w


def test_paper_profile_reproduces_headline_ratios():
    """The acceptance criterion: paper_fpga_45nm over the checked-in
    measured trace reproduces −53.3% latency, −42% accesses, −52.2%
    energy within ±3 points."""
    hw = load_profile(PAPER)
    trace = paper_trace()
    from repro.core import CmaxConfig
    cfg = CmaxConfig()
    pct = lambda a, b: 100.0 * (b - a) / b
    lat, acc, ene = [], [], []
    for stage_stats in trace["windows"]:
        _, e_c = account_window(stage_stats, cfg, hw, camel=True,
                                n_total=trace["n_total"])
        _, e_b = account_window(stage_stats, cfg, hw, camel=False,
                                n_total=trace["n_total"])
        a_c, _ = account_window(stage_stats, cfg, hw, camel=True,
                                n_total=trace["n_total"])
        a_b, _ = account_window(stage_stats, cfg, hw, camel=False,
                                n_total=trace["n_total"])
        lat.append((e_c["latency_s"], e_b["latency_s"]))
        acc.append((a_c.total_accesses, a_b.total_accesses))
        ene.append((e_c["e_total_uj"], e_b["e_total_uj"]))
    mean_pct = lambda pairs: pct(np.mean([p[0] for p in pairs]),
                                 np.mean([p[1] for p in pairs]))
    assert abs(mean_pct(lat) - 53.3) <= 3.0
    assert abs(mean_pct(acc) - 42.0) <= 3.0
    assert abs(mean_pct(ene) - 52.2) <= 3.0


# ---------------------------------------------------------------------------
# BudgetScheduler properties
# ---------------------------------------------------------------------------

_HW = load_profile(PAPER)


def _plans_from(seed, n_windows, n_stages, max_iters):
    rng = np.random.default_rng(seed)
    plans = []
    for _ in range(n_windows):
        stages = tuple(
            StagePlan(cost_uj=float(rng.uniform(0.5, 20.0)),
                      cost_ms=float(rng.uniform(0.05, 2.0)),
                      gain0=float(rng.uniform(0.0, 0.1)),
                      decay=float(rng.uniform(0.2, 0.9)),
                      max_iters=max_iters)
            for _ in range(n_stages))
        plans.append(WindowPlan(stages=stages))
    return plans


@settings(max_examples=30)
@given(st.integers(0, 10_000), st.integers(1, 4), st.integers(1, 3),
       st.integers(1, 8), st.floats(0.0, 400.0), st.floats(0.0, 400.0))
def test_allocation_monotone_in_budget(seed, B, S, max_iters, b1, b2):
    """More budget never yields fewer total iterations."""
    sched = BudgetScheduler(_HW)
    plans = _plans_from(seed, B, S, max_iters)
    lo, hi = sorted((b1, b2))
    a_lo = sched.allocate(plans, budget_uj=lo)
    a_hi = sched.allocate(plans, budget_uj=hi)
    assert a_hi.total_iters >= a_lo.total_iters
    # per-slot monotone too: the bigger budget extends the same prefix
    assert np.all(a_hi.iters >= a_lo.iters)


@settings(max_examples=20)
@given(st.integers(0, 10_000), st.integers(1, 4), st.integers(1, 3),
       st.integers(1, 8))
def test_zero_budget_grants_floor(seed, B, S, max_iters):
    """Zero budget still estimates: exactly the 1-iteration floor."""
    sched = BudgetScheduler(_HW)
    plans = _plans_from(seed, B, S, max_iters)
    alloc = sched.allocate(plans, budget_uj=0.0)
    assert np.all(alloc.iters == np.minimum(1, max_iters))
    assert alloc.total_iters == B * S * min(1, max_iters)


@settings(max_examples=20)
@given(st.integers(0, 10_000), st.integers(1, 4), st.integers(1, 3),
       st.integers(1, 8), st.floats(0.0, 400.0))
def test_allocation_respects_caps_and_budget(seed, B, S, max_iters, budget):
    sched = BudgetScheduler(_HW)
    plans = _plans_from(seed, B, S, max_iters)
    alloc = sched.allocate(plans, budget_uj=budget)
    assert np.all(alloc.iters <= max_iters)
    assert np.all(alloc.iters >= 1)
    # spend beyond the unconditional floor never exceeds the budget
    floor_cost = sum(min(1, sp.max_iters) * sp.cost_uj
                     for p in plans for sp in p.stages)
    assert alloc.spent_uj <= max(budget, floor_cost) + 1e-9


def test_no_budget_means_uncapped():
    sched = BudgetScheduler(_HW)
    plans = _plans_from(0, 2, 3, 7)
    alloc = sched.allocate(plans)
    assert isinstance(alloc, Allocation)
    assert np.all(alloc.iters == 7)
    assert np.isnan(alloc.spent_uj)


def test_plan_window_costs_scale_with_events():
    from repro.core import CmaxConfig
    sched = BudgetScheduler(_HW)
    cfg = CmaxConfig()
    small = sched.plan_window(cfg, 1000)
    big = sched.plan_window(cfg, 40000)
    assert len(small.stages) == len(cfg.stages)
    for s, b in zip(small.stages, big.stages):
        assert b.cost_uj > s.cost_uj
        assert s.max_iters == b.max_iters


def test_min_iters_validation():
    with pytest.raises(ValueError):
        BudgetScheduler(_HW, min_iters=0)


# ---------------------------------------------------------------------------
# budgeted pipeline + QoS serving
# ---------------------------------------------------------------------------


def _fast_cfg():
    from repro.core import CmaxConfig, StageConfig
    from helpers import small_camera
    stages = (
        StageConfig(scale=4, tau=1e-4, max_iters=6, blur_taps=3,
                    blur_sigma=1.0, keep_ratio=0.25, step_scale=4.0),
        StageConfig(scale=2, tau=1e-4, max_iters=6, blur_taps=3,
                    blur_sigma=1.0, keep_ratio=0.5, step_scale=2.0),
    )
    return CmaxConfig(camera=small_camera(), stages=stages)


def test_budgeted_pipeline_caps():
    import jax.numpy as jnp
    from repro.core import estimate_window, estimate_window_budgeted
    from helpers import random_window
    cfg = _fast_cfg()
    ev = random_window(n=512, cam=cfg.camera, seed=3)
    om0 = jnp.zeros(3, jnp.float32)
    ref = estimate_window(ev, om0, cfg)
    wide = estimate_window_budgeted(ev, om0, jnp.asarray([99, 99],
                                                         jnp.int32), cfg)
    assert np.array_equal(np.asarray(ref.omega), np.asarray(wide.omega))
    capped = estimate_window_budgeted(ev, om0, jnp.asarray([1, 2],
                                                           jnp.int32), cfg)
    assert int(capped.stages[0].iters) <= 1
    assert int(capped.stages[1].iters) <= 2


def test_serve_qos_budgeted_vs_standard():
    from repro.data import events as ev_data
    from repro.launch.serve import (AsyncBatchedEstimationService,
                                    InlineExecutor, QosClass)
    cfg = _fast_cfg()
    policy = ev_data.pow2_policy(min_bucket=256)

    def run(qos_classes, qos_kw):
        svc = AsyncBatchedEstimationService(
            cfg, policy=policy, executor=InlineExecutor(),
            qos_classes=qos_classes)
        spec = ev_data.SequenceSpec(
            name="s0", n_windows=2, events_per_window=512, seed=11,
            camera=cfg.camera, omega_scale=3.0, window_dt=0.02)
        wins, _, _ = ev_data.make_sequence(spec)
        for w in ev_data.ragged_from_sequence(wins, [400, 512]):
            svc.submit("s0", w, **qos_kw)
        return svc, svc.drain()

    _, r_std = run(None, {})
    hi_svc, r_hi = run([QosClass("q", budget_uj=1e9)], {"qos": "q"})
    lo_svc, r_lo = run([QosClass("q", budget_uj=0.0)], {"qos": "q"})

    # a generous budget behaves exactly like the standard class
    for a, b in zip(sorted(r_hi, key=lambda r: r.seq),
                    sorted(r_std, key=lambda r: r.seq)):
        assert np.allclose(a.omega, b.omega)
        assert a.iters == b.iters
        assert a.qos == "q" and b.qos == "standard"
    # zero budget floors every stage at one iteration, still status ok
    assert all(r.status == "ok" for r in r_lo)
    assert all(all(i <= 1 for i in r.iters) for r in r_lo)
    assert lo_svc.stats["budgeted_windows"] == 2
    assert hi_svc.stats["budget_spent_uj"] > 0


def test_serve_unknown_qos_rejected():
    from repro.launch.serve import AsyncBatchedEstimationService
    from helpers import random_window
    svc = AsyncBatchedEstimationService(_fast_cfg())
    with pytest.raises(ValueError, match="nope"):
        svc.submit("s0", _Ragged(random_window(n=512)), qos="nope")


@dataclasses.dataclass
class _Ragged:
    """Minimal window-like wrapper exposing .n for submit-time bucketing."""
    win: object

    @property
    def n(self):
        return int(self.win.x.shape[0])

    def __getattr__(self, k):
        return getattr(self.win, k)
