"""The reduction of the program's own spans and scopes
(`bench/program_trace.py`) on hand-made planes, and the readers of the
per-layer metrics that read it."""
import pytest

from bench import harness, program_trace as pt, trace

MS = 1_000_000

HLO = """HloModule jit_estimate_batch_donated, is_scheduled=true
ENTRY %main {
  %fusion.1 = f32[8]{0} fusion(%a), kind=kLoop, calls=%f1, metadata={op_name="jit(estimate_batch_donated)/cmax.stage0/cmax.sort/sort" source_file="x.py"}
  %fusion.2 = f32[8]{0} fusion(%b), kind=kLoop, calls=%f2, metadata={op_name="jit(estimate_batch_donated)/cmax.stage0/while/body/cmax.engine_pass/jit(batched_engine_stats)/cmax.bin_taps/sort"}
  %custom-call.3 = f32[8]{0} custom-call(%c), custom_call_target="tpu_custom_call", metadata={op_name="jit(estimate_batch_donated)/cmax.stage0/while/body/cmax.engine_pass/jit(batched_engine_stats)/cmax.megakernel/pallas_call"}
  %fusion.5 = f32[8]{0} fusion(%fusion.1), kind=kCustom, calls=%fused_computation.5
  ROOT %tuple.4 = (f32[8]{0}) tuple(%fusion.1), metadata={op_name="jit(estimate_batch_donated)"}
}

%fused_computation.5 (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  %exp.1 = f32[8]{0} exponential(%param_0), metadata={op_name="jit(estimate_batch_donated)/cmax.stage1/while/body/cmax.update/exp"}
  %add.2 = f32[8]{0} add(%exp.1, %param_0), metadata={op_name="jit(estimate_batch_donated)/cmax.stage1/while/body/cmax.update/add"}
  ROOT %fusion.6 = f32[8]{0} fusion(%add.2), kind=kLoop, calls=%fused_computation.6
}

%fused_computation.6 (param_0.1: f32[8]) -> f32[8] {
  ROOT %neg.3 = f32[8]{0} negate(%param_0.1), metadata={op_name="jit(estimate_batch_donated)/cmax.stage1/cmax.sort/neg"}
}
"""


def test_scope_map_keeps_the_cmax_scopes_of_each_instruction():
    got = pt.scope_map(HLO)
    assert {k: got[k] for k in ("fusion.1", "fusion.2", "custom-call.3")} \
        == {"fusion.1": "cmax.stage0/cmax.sort",
            "fusion.2": "cmax.stage0/cmax.engine_pass/cmax.bin_taps",
            "custom-call.3": "cmax.stage0/cmax.engine_pass/cmax.megakernel"}
    # a fusion without an op_name of its own is named by the work it
    # fuses, nested fusions included: 2 x update against 1 x sort
    assert got["fusion.5"] == "cmax.stage1/cmax.update"
    assert "tuple.4" not in got and "param_0" not in got
    assert pt.module_name("jit_f(123)") == "jit_f"
    assert pt.scope_maps([HLO, HLO.replace("fusion.1 ", "fusion.9 ")]) \
        ["jit_estimate_batch_donated"]["fusion.9"] == "cmax.stage0/cmax.sort"


def planes():
    """One device: module A (a scoped batch function) runs [0, 4] ms and
    [6, 9] ms, module B (unscoped, with an instruction of the same name
    as one of A's) [10, 11] ms; host spans of two batches."""
    ops = [(0, 1 * MS, "fusion.1"), (1 * MS, 3 * MS, "fusion.2"),
           (3 * MS, 4 * MS, "custom-call.3"),
           (6 * MS, 9 * MS, "fusion.2"),
           (10 * MS, 11 * MS, "fusion.1")]
    modules = [(0, 4 * MS, "jit_estimate_batch_donated(77)"),
               (6 * MS, 9 * MS, "jit_estimate_batch_donated(77)"),
               (10 * MS, 11 * MS, "jit__pad(5)")]
    host = [(-1 * MS, 12 * MS, trace.WINDOW_SPAN, {}),
            (-1 * MS, 0, "serve.dispatch", {"batch": 4}),
            (4 * MS, 7 * MS, "serve.poll", {}),
            (4 * MS, 5 * MS, "serve.harvest", {"batch": 4}),
            (5 * MS, 6 * MS, "serve.launch", {"batch": 5}),
            (5 * MS, 5.5 * MS, "serve.make_batch", {"batch": 5}),
            (5.5 * MS, 6 * MS, "serve.dispatch", {"batch": 5}),
            (9 * MS, 9.5 * MS, "serve.harvest", {"batch": 5})]
    return [(ops, modules)], host


def test_scopes_are_looked_up_in_the_module_that_ran_the_operation():
    devices, host = planes()
    maps = {"jit_estimate_batch_donated": pt.scope_map(HLO)}
    red = pt.reduce_program(devices, host, maps)
    # B's fusion.1 is not A's: it is unscoped
    assert red["scope_s"] == pytest.approx({
        "cmax.stage0/cmax.sort": 1e-3,
        "cmax.stage0/cmax.engine_pass/cmax.bin_taps": 5e-3,
        "cmax.stage0/cmax.engine_pass/cmax.megakernel": 1e-3,
        "unscoped": 1e-3})


def test_idle_is_divided_among_the_innermost_program_spans():
    devices, host = planes()
    red = pt.reduce_program(devices, host, {})
    busy = trace.reduce_planes(
        [[(a, b, n, False) for a, b, n in devices[0][0]]],
        [(a, b, n) for a, b, n, _ in host])
    idle = red["idle_by_program_span"]
    # gaps [-1, 0] under dispatch 4; [4, 6]: harvest, make_batch,
    # dispatch; [9, 10]: harvest 0.5 ms, the rest outside; [11, 12] outside
    assert idle == pytest.approx({
        "serve.dispatch": 1.5e-3, "serve.harvest": 1.5e-3,
        "serve.make_batch": 0.5e-3, "outside": 1.5e-3})
    assert sum(idle.values()) == pytest.approx(
        busy["window_s"] - busy["busy_s"], abs=1e-9)
    assert red["program_spans"]["serve.harvest"] == [2, pytest.approx(
        1.5e-3)]
    assert red["program_spans"]["serve.poll"] == [1, pytest.approx(3e-3)]


def test_innermost_segments_of_nested_spans():
    segs = pt.innermost_segments([(0, 10, "p"), (2, 5, "c"), (6, 8, "d"),
                                  (12, 13, "q")])
    assert segs == [(0, 2, "p"), (2, 5, "c"), (5, 6, "p"), (6, 8, "d"),
                    (8, 10, "p"), (12, 13, "q")]


def test_executions_are_matched_to_batches_in_dispatch_order():
    devices, host = planes()
    maps = {"jit_estimate_batch_donated": pt.scope_map(HLO)}
    red = pt.reduce_program(devices, host, maps)
    # engine-pass time of batches 4 and 5, whose executions lie inside
    # the window
    assert red["batches"] == [[4, pytest.approx(3e-3)],
                              [5, pytest.approx(3e-3)]]
    # batch ids count up: an execution dispatched before the trace began
    # is matched by its harvest (6 here), one whose batch was not
    # harvested before the trace stopped matches nothing (9)
    dispatch = [(10, 7), (30, 8), (50, 9)]
    harvest = {6: 8, 7: 25, 8: 45}
    assert pt.match_batches([(5, 7), (12, 20), (31, 40), (51, 52)],
                            dispatch, harvest) == [6, 7, 8, None]
    # two long batches, neither both dispatched and harvested in the trace
    assert pt.match_batches([(7, 4887), (4927, 10155), (10193, 10194)],
                            [(4926, 7), (10192, 8)], {5: 8, 6: 4934}) \
        == [6, None, None]
    assert pt.match_batches([(1, 2)], [], {}) == [None]


def record():
    """A record as the harness builds it, with the keys above in its
    trace: batch 4 (class 2, slowest window 3 + 5 passes) and batch 5
    (class 1, 2 + 1)."""
    spans = [{"batch": 4, "batch_b": 2}, {"batch": 4, "batch_b": 2},
             {"batch": 5, "batch_b": 1}]
    dec = [("a", 4, 0, 0, 2), ("a", 4, 0, 1, 4), ("b", 4, 1, 0, 1),
           ("b", 4, 1, 1, 0), ("c", 5, 0, 0, 1), ("c", 5, 0, 1, 0)]
    decisions = [{"stream_id": s, "batch": b, "slot": i, "stage": st,
                  "iters": it} for s, b, i, st, it in dec]
    return {
        "spans": spans, "decisions": decisions,
        "registry": {"repro_serving_slot_passes_total": 19,
                     "repro_serving_window_passes_total": 14},
        "trace": {"busy_s": 8e-3, "window_s": 16e-3,
                  "scope_s": {"cmax.stage0/cmax.sort": 1e-3,
                              "cmax.stage0/cmax.engine_pass/"
                              "cmax.bin_taps": 5e-3,
                              "cmax.stage1/cmax.engine_pass": 1e-3,
                              "unscoped": 1e-3},
                  "idle_by_program_span": {
                      "serve.dispatch": 1.5e-3, "serve.harvest": 1.6e-3,
                      "serve.make_batch": 0.5e-3, "serve.launch": 0.2e-3,
                      "serve.poll": 1e-3, "outside": 1.2e-3},
                  "batches": [[4, 4e-3], [5, 1.5e-3], [6, 1e-3]]},
    }


def test_readers_of_the_program_trace():
    rec = record()
    read = lambda name: harness.reader(name)(rec)
    assert read("engine.pass_share") == pytest.approx(75.0)
    assert read("megakernel.prologue_share") == pytest.approx(62.5)
    # batch 6 has no decisions: left out; 2 x (3 + 5) + 1 x (2 + 1) slot
    # passes hold 5.5 ms
    assert read("engine.slot_pass_us") == pytest.approx(5.5e-3 / 19 * 1e6)
    assert read("service.launch_idle_share") == pytest.approx(
        100 * 2.2e-3 / 16e-3)
    assert read("service.harvest_idle_share") == pytest.approx(10.0)
    assert read("controller.lockstep_waste") == pytest.approx(
        100 * (1 - 14 / 19))


def test_readers_find_nothing_in_a_record_without_the_new_keys():
    """A run of a program or a trace reduction that lacks the spans,
    scopes and counters reads None, and raises nothing."""
    rec = record()
    rec["registry"] = {}
    rec["trace"] = {"busy_s": 8e-3, "window_s": 16e-3}
    for name in ("engine.pass_share", "megakernel.prologue_share",
                 "engine.slot_pass_us", "service.launch_idle_share",
                 "service.harvest_idle_share", "controller.lockstep_waste"):
        assert harness.reader(name)(rec) is None, name
        assert harness.reader(name)(dict(rec, trace=None)) is None, name


def test_lockstep_waste_is_zero_at_batch_class_one():
    rec = {"registry": {"repro_serving_slot_passes_total": 57,
                        "repro_serving_window_passes_total": 57}}
    assert harness.reader("controller.lockstep_waste")(rec) == 0.0


def test_a_trace_without_the_programs_names_reduces_to_nothing_named(
        tmp_path):
    """The trace recorded on the v5e before the program had spans and
    scopes (`v5e_one_camera`): every operation is unscoped, every idle
    gap outside, and the idle still sums to the window's."""
    import lzma
    import os
    path = tmp_path / "t.xplane.pb"
    data = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
    with lzma.open(os.path.join(data, "v5e_one_camera.xplane.pb.xz")) as f:
        path.write_bytes(f.read())
    devices, host = pt.read_program_planes(str(path))
    red = pt.reduce_program(devices, host, {})
    base = trace.reduce_trace(str(tmp_path))
    assert list(red["scope_s"]) == ["unscoped"]
    assert red["scope_s"]["unscoped"] == pytest.approx(base["busy_s"],
                                                       rel=1e-6)
    assert list(red["idle_by_program_span"]) == ["outside"]
    assert red["idle_by_program_span"]["outside"] == pytest.approx(
        base["window_s"] - base["busy_s"], rel=1e-9)
    assert red["program_spans"] == {} and red["batches"] == []


def test_recorded_megakernel_trace_lies_under_the_programs_scopes(
        tmp_path):
    """A 3 s window of cmax240-mk.backlog recorded on one TPU v5e with the
    program's regions and scopes (`bench/program_trace.py`), reduced with
    the scope map taken from the compiled HLO of its batch function (the
    instructions the trace holds): at least 90% of the device's busy time
    lies under `cmax.*` scopes, the idle time is divided whole among the
    `serve.*` regions, and the one batch run and harvested inside the
    window is found with its engine-pass time."""
    import json
    import lzma
    import os
    data = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
    path = tmp_path / "t.xplane.pb"
    with lzma.open(os.path.join(data, "v5e_mk_backlog.xplane.pb.xz")) as f:
        path.write_bytes(f.read())
    with open(os.path.join(data, "v5e_mk_backlog.scope_map.json")) as f:
        maps = json.load(f)
    devices, host = pt.read_program_planes(str(path))
    red = pt.reduce_program(devices, host, maps)
    base = trace.reduce_trace(str(tmp_path))
    busy = base["busy_s"]
    scoped = busy - red["scope_s"].get("unscoped", 0.0)
    assert scoped >= 0.9 * busy
    assert sum(red["scope_s"].values()) == pytest.approx(busy, rel=1e-6)
    idle = red["idle_by_program_span"]
    assert sum(idle.values()) == pytest.approx(base["window_s"] - busy,
                                               rel=1e-9)
    for name in ("serve.poll", "serve.launch", "serve.make_batch",
                 "serve.dispatch", "serve.harvest"):
        assert red["program_spans"][name][0] >= 1
    ids = sorted({st["batch"] for _, _, n, st in host
                  if n == "serve.launch"})
    assert ids == [0, 1, 2]
    (batch, engine_s), = red["batches"]
    assert batch == 0 and 0 < engine_s < busy
    # the prologue, the Mosaic kernel and, in this window, the exact slow
    # path of a window over the kernel's capacity
    for inner in ("cmax.bin_taps", "cmax.megakernel",
                  "cmax.spill_slow_path"):
        assert any(k.split("/")[-1] == inner and v > 0
                   for k, v in red["scope_s"].items()), inner
