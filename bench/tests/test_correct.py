"""The comparison that decides `correct`, at a size a test run holds.

A whole run of the harness (generation, warm-up, the served window, the
check) on a small camera, with the look for a chip skipped: a sound run
is correct; the control (the reference in bfloat16 in the program's
place) fails the comparison; and each fault planted in the timed path
underneath the service makes `correct` false. The cells run on one chip,
so there is no exchange between chips to leave out.
"""
import copy
import dataclasses
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEED = 2 ** 31 + 17
SECONDS = 1.0


def small_spec(config: str, loop: str = "closed") -> harness.CellSpec:
    """The configuration's file and the backlog mix, cut to a 64x48
    camera and 2,000-event windows; stages, limits and service settings
    otherwise as in the files."""
    cfg = harness.load_json(os.path.join(BENCH, "configs", f"{config}.json"))
    cfg["camera"] = {"width": 64, "height": 48, "fx": 60.0, "fy": 60.0,
                     "cx": 32.0, "cy": 24.0}
    for st in cfg["stages"]:
        st["max_iters"] = 8
    cfg["service"] = dict(cfg["service"], max_batch=2, length_classes=[2048])
    cfg["engine_capacity"] = 2048
    mix = harness.load_json(os.path.join(BENCH, "traffic", "backlog.json"))
    mix.update(cameras=4, windows_per_camera=4, events_per_window=2000,
               order_group=2)
    if loop == "open":
        mix.update(loop="open", rate_per_s=8.0)
    for sc in mix["scenes"]:
        sc["n_features"] = 30
    bench = harness.load_json(os.path.join(os.path.dirname(BENCH),
                                           "BENCHMARK.json"))
    want = ("windows_per_s",) if loop == "closed" else ()
    e2e = [m for m in bench["end_to_end"]
           if m["name"] in want + ("rmse_fixed_rad_s", "setup_s")]
    return harness.CellSpec(f"small.{loop}", cfg, mix, e2e, [])


def run(spec, control=False, seconds=SECONDS):
    return harness.run(spec, SEED, seconds, trace=False,
                       t_process=time.perf_counter(), control=control,
                       require_tpu=False)


def failing(out):
    return sorted(k for k, n in out["checks"].items()
                  if not k.startswith("control.") and n["value"] > n["limit"])


def test_config_files_are_the_programs_deployment():
    from repro.configs import cmax_camel
    for name, want in (("cmax240-ref", cmax_camel.CONFIG),
                       ("cmax240-mk", cmax_camel.MEGAKERNEL)):
        cfg = harness.load_json(os.path.join(BENCH, "configs",
                                             f"{name}.json"))
        assert harness.cmax_config(cfg) == want
        assert cfg["events_per_window"] == cmax_camel.EVENTS_PER_WINDOW


@pytest.mark.parametrize("loop", ["closed", "open"])
def test_sound_run_is_correct(loop):
    out = run(small_spec("cmax240-ref", loop))
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert set(out["metrics"]) == {m["name"] for m in
                                   small_spec("cmax240-ref", loop).end_to_end}
    # the accuracy set is complete however few windows the window served
    mix = small_spec("cmax240-ref", loop).mix
    assert out["attempted"] >= \
        mix["cameras"] * mix["accuracy_windows_per_camera"]


def test_control_fails():
    out = run(small_spec("cmax240-ref"), control=True)
    assert out["correct"]
    lim = out["checks"]
    assert lim["control.engine_rel_err"]["value"] > \
        lim["control.engine_rel_err"]["limit"]
    assert lim["control.controller_mismatch"]["value"] > \
        lim["control.controller_mismatch"]["limit"]


def _state_unchanged(real):
    def fn(w, o, cfg):
        res = real(w, jnp.array(o), cfg)
        stages = tuple(st._replace(omega_entry=o, omega_exit=o,
                                   iters=st.iters * 0, v_final=st.v_entry)
                       for st in res.stages)
        return res._replace(omega=o, stages=stages)
    return fn


def _half_batch(real):
    def fn(w, o, cfg):
        b = o.shape[0]
        h = max(1, b // 2)
        res = real(jax.tree.map(lambda a: a[:h], w), o[:h], cfg)
        return jax.tree.map(
            lambda a: jnp.concatenate([a] * (-(-b // h)))[:b], res)
    return fn


def _answer_altered(real):
    def fn(w, o, cfg):
        res = real(w, o, cfg)
        return res._replace(omega=res.omega.at[0, 0].add(1e-3))
    return fn


@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch,
                                   _answer_altered])
def test_fault_in_timed_path_is_not_correct(fault, monkeypatch):
    from repro.core import pipeline
    monkeypatch.setattr(pipeline, "estimate_batch_donated",
                        fault(pipeline.estimate_batch))
    out = run(small_spec("cmax240-ref"))
    assert not out["correct"], out["checks"]
    assert failing(out)


def test_megakernel_run_is_correct():
    # interpreted on the CPU, the megakernel needs a longer window to
    # complete two batches in it
    out = run(small_spec("cmax240-mk"), seconds=4.0)
    assert out["correct"], out["checks"]
