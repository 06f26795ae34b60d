"""Reduce a profiler trace (`.xplane.pb`) to the benchmark's device numbers.

  busy_s       length of the union of the device's operation intervals
               inside the traced window, averaged over the device planes
  window_s     length of the traced window: the host span WINDOW_SPAN that
               the harness writes around its measured loop
  device_ops   device self time per operation (less the operations nested
               in it, as a `while` holds its body), summed by name,
               longest first
  custom_call_s  device time of Mosaic (Pallas) kernels: operations whose
               HLO text is a custom call
  idle_gaps    device idle time inside the window, summed by what the host
               was doing: the innermost of the harness's own host spans
               (`bench.*`) that covers half of a gap, else the one that
               overlaps it most, else "host:other"

Device operations are the events of a device plane's OPS_LINE ("XLA Ops");
a trace with no device plane is an error, as is a window the trace does
not hold. Host spans are `jax.profiler.TraceAnnotation`s on the host
plane, on the same clock as the device events.
"""
from __future__ import annotations

import bisect
import glob
import os
from collections import defaultdict
from typing import Dict, List, Tuple

OPS_LINE = "XLA Ops"
WINDOW_SPAN = "bench.window"
HOST_PREFIX = "bench."
CUSTOM_CALL_MARKS = ("custom-call", "custom_call", "tpu_custom_call")


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {trace_dir}, "
                           f"found {len(paths)}")
    return paths[0]


def union_length(intervals: List[Tuple[float, float]]) -> float:
    total, end = 0.0, None
    start = None
    for a, b in sorted(intervals):
        if end is None or a > end:
            if end is not None:
                total += end - start
            start, end = a, b
        else:
            end = max(end, b)
    if end is not None:
        total += end - start
    return total


def gaps(intervals: List[Tuple[float, float]], lo: float, hi: float
         ) -> List[Tuple[float, float]]:
    """Maximal sub-intervals of [lo, hi] that no interval covers."""
    out, cur = [], lo
    for a, b in sorted(intervals):
        if b <= cur:
            continue
        if a > cur:
            out.append((cur, min(a, hi)))
        cur = max(cur, b)
        if cur >= hi:
            break
    if cur < hi:
        out.append((cur, hi))
    return [(a, b) for a, b in out if b > a]


def _is_custom_call(hlo_text: str) -> bool:
    text = hlo_text.lower()
    return any(m in text for m in CUSTOM_CALL_MARKS)


def _host_activity(spans, starts, longest, ga, gb) -> str:
    """What the host was doing in the idle gap [ga, gb]: the shortest
    (innermost) host span that covers at least half of it, else the span
    that overlaps it most, else "host:other"."""
    lo_i = bisect.bisect_left(starts, ga - longest)
    hi_i = bisect.bisect_left(starts, gb)
    best, label, inner = 0.0, "host:other", None
    for a, b, n in spans[lo_i:hi_i]:
        ov = min(b, gb) - max(a, ga)
        if ov <= 0:
            continue
        if ov > best:
            best, label = ov, n
        if 2 * ov >= gb - ga and (inner is None or b - a < inner[0]):
            inner = (b - a, n)
    return inner[1] if inner else label


def short_name(hlo_text: str) -> str:
    """`%fusion.528 = f32[...] fusion(...), kind=kCustom, ...` ->
    `fusion.528`, with a custom call's target where it has one."""
    name = hlo_text.split(" = ", 1)[0].lstrip("%")
    if "custom_call_target=" in hlo_text:
        target = hlo_text.split("custom_call_target=", 1)[1]
        name += " " + target.split(",", 1)[0].strip('"')
    return name


def self_times(ops) -> Dict[str, float]:
    """Device time per operation, less the time of operations nested
    inside it on the same line (a `while` holds its body's ops)."""
    out: Dict[str, float] = defaultdict(float)
    stack: list = []          # [end, name, child time]
    for a, b, name in sorted(ops, key=lambda o: (o[0], -o[1])):
        while stack and stack[-1][0] <= a:
            end, n, child = stack.pop()
            out[n] -= child
        if stack:
            stack[-1][2] += b - a
        out[name] += b - a
        stack.append([b, name, 0.0])
    while stack:
        _, n, child = stack.pop()
        out[n] -= child
    return out


def read_planes(path: str):
    """(device planes: [[(start_ns, end_ns, name, is_custom_call)]],
    host spans: [(start_ns, end_ns, name)])."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, host = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            ops, names = [], {}
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for e in line.events:
                    if e.name not in names:
                        names[e.name] = (short_name(e.name),
                                         _is_custom_call(e.name))
                    short, cc = names[e.name]
                    ops.append((e.start_ns, e.start_ns + e.duration_ns,
                                short, cc))
            if ops:
                devices.append(ops)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HOST_PREFIX):
                        host.append((e.start_ns,
                                     e.start_ns + e.duration_ns, e.name))
    return devices, host


def reduce_planes(devices, host, top: int = 10) -> dict:
    if not devices:
        raise RuntimeError("the trace holds no device operations")
    win = [(a, b) for a, b, n in host if n == WINDOW_SPAN]
    if len(win) != 1:
        raise RuntimeError(f"expected one {WINDOW_SPAN!r} host span, "
                           f"found {len(win)}")
    lo, hi = win[0]
    spans = sorted((a, b, n) for a, b, n in host if n != WINDOW_SPAN)
    starts = [a for a, _, _ in spans]
    longest = max((b - a for a, b, _ in spans), default=0)
    busy, custom = [], []
    by_name: Dict[str, float] = defaultdict(float)
    idle: Dict[str, float] = defaultdict(float)
    for ops in devices:
        clipped = [(max(a, lo), min(b, hi), n, cc) for a, b, n, cc in ops
                   if b > lo and a < hi]
        iv = [(a, b) for a, b, _, _ in clipped]
        busy.append(union_length(iv))
        custom.append(union_length([(a, b) for a, b, _, cc in clipped
                                    if cc]))
        for n, t in self_times([(a, b, n) for a, b, n, _ in
                                clipped]).items():
            by_name[n] += t * 1e-9 / len(devices)
        for ga, gb in gaps(iv, lo, hi):
            idle[_host_activity(spans, starts, longest, ga, gb)] += \
                (gb - ga) * 1e-9 / len(devices)
    nd = len(devices)
    rank = lambda d: [[k, v] for k, v in sorted(d.items(),
                                                key=lambda kv: -kv[1])][:top]
    return {
        "busy_s": sum(busy) * 1e-9 / nd,
        "window_s": (hi - lo) * 1e-9,
        "custom_call_s": sum(custom) * 1e-9 / nd,
        "devices": nd,
        "device_ops": rank(by_name),
        "idle_gaps": rank(idle),
    }


def reduce_trace(trace_dir: str, top: int = 10) -> dict:
    devices, host = read_planes(find_xplane(trace_dir))
    return reduce_planes(devices, host, top)
