"""The trace reduction, on hand-made planes and on a trace recorded on a
TPU v5e."""
import json
import lzma
import os

import pytest

from bench import trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_union_and_gaps():
    iv = [(0, 10), (5, 15), (20, 30), (29, 31)]
    assert trace.union_length(iv) == 15 + 11
    assert trace.gaps(iv, -5, 40) == [(-5, 0), (15, 20), (31, 40)]
    assert trace.gaps([], 0, 3) == [(0, 3)]


def test_reduce_planes_busy_idle_and_attribution():
    ms = 1_000_000
    device = [[(0, 3 * ms, "while.1", False),
               (0, 1 * ms, "fusion.1", False),
               (1 * ms, 3 * ms, "fusion.2", False),
               (6 * ms, 8 * ms, "megakernel", True),
               (11 * ms, 13 * ms, "fusion.1", False)]]
    host = [(-1 * ms, 12 * ms, "bench.window"),
            (2 * ms, 7 * ms, "bench.poll"),
            (3 * ms, 5 * ms, "bench.make_batch"),
            (8 * ms, 9 * ms, "bench.sleep")]
    red = trace.reduce_planes(device, host)
    assert red["window_s"] == pytest.approx(13e-3)
    # busy inside [-1, 12] ms: [0, 3] + [6, 8] + [11, 12]
    assert red["busy_s"] == pytest.approx(6e-3)
    assert red["custom_call_s"] == pytest.approx(2e-3)
    ops = dict((k, v) for k, v in red["device_ops"])
    # self time: the while's body ops are not the while's own
    assert ops == pytest.approx({"while.1": 0.0, "fusion.1": 2e-3,
                                 "fusion.2": 2e-3, "megakernel": 2e-3})
    idle = dict((k, v) for k, v in red["idle_gaps"])
    # gaps: [-1, 0] none; [3, 6] make_batch covers 2 of 3 ms, innermost;
    # [8, 11] sleep covers 1 of 3 ms, the most of any span
    assert idle == pytest.approx({"host:other": 1e-3,
                                  "bench.make_batch": 3e-3,
                                  "bench.sleep": 3e-3})


def test_short_name():
    assert trace.short_name(
        "%fusion.528 = f32[8]{0} fusion(f32[8]{0} %a), kind=kCustom") == \
        "fusion.528"
    assert trace.short_name(
        '%custom-call.3 = f32[8]{0} custom-call(%a), '
        'custom_call_target="tpu_custom_call", api_version=1') == \
        "custom-call.3 tpu_custom_call"


def test_window_span_required():
    with pytest.raises(RuntimeError):
        trace.reduce_planes([[(0, 1, "a", False)]], [])
    with pytest.raises(RuntimeError):
        trace.reduce_planes([], [(0, 1, trace.WINDOW_SPAN)])


def test_recorded_chip_trace(tmp_path):
    """A 1.5 s window of cmax240-ref.one-camera recorded on one TPU v5e
    (`--trace 1 --keep-trace`), reduced here: the numbers must be the ones
    the chip run printed, and the busy time must agree with the union of
    the device's XLA module executions, a second line of the same plane
    that the reduction does not read."""
    from jax.profiler import ProfileData
    path = tmp_path / "t.xplane.pb"
    with lzma.open(os.path.join(DATA, "v5e_one_camera.xplane.pb.xz")) as f:
        path.write_bytes(f.read())
    devices, host = trace.read_planes(str(path))
    red = trace.reduce_planes(devices, host)
    with open(os.path.join(DATA, "v5e_one_camera.reduced.json")) as f:
        want = json.load(f)
    for key in ("busy_s", "window_s", "custom_call_s"):
        assert red[key] == pytest.approx(want[key], rel=1e-12)
    assert red["device_ops"] == want["device_ops"]
    assert red["idle_gaps"] == want["idle_gaps"]
    assert red["devices"] == 1 and 0 < red["busy_s"] < red["window_s"]
    idle = sum(v for _, v in red["idle_gaps"])
    assert idle == pytest.approx(red["window_s"] - red["busy_s"], rel=1e-9)

    lo, hi = [(a, b) for a, b, n in host if n == trace.WINDOW_SPAN][0]
    plane = [p for p in ProfileData.from_file(str(path)).planes
             if p.name.startswith("/device:TPU")][0]
    modules = [(max(e.start_ns, lo), min(e.start_ns + e.duration_ns, hi))
               for line in plane.lines if line.name == "XLA Modules"
               for e in line.events
               if e.start_ns + e.duration_ns > lo and e.start_ns < hi]
    assert trace.union_length(modules) * 1e-9 == pytest.approx(
        red["busy_s"], rel=0.01)
