"""Read the program's own names in a profiler trace: its host spans
(`serve.*`, `telemetry/spans.Tracer.region`) and its device scopes
(`cmax.*`, `jax.named_scope` in `core/pipeline.py` and `kernels/ops.py`).

  scope_s      device self time inside the traced window per chain of
               `cmax.*` scopes of the operation, outermost first
               (`cmax.stage2/cmax.engine_pass/cmax.bin_taps`); `unscoped`
               holds the rest. An operation's scopes are its `op_name` in
               the compiled HLO of the module it ran in: the trace names
               an operation only by its HLO instruction, and instruction
               names repeat across modules, so each operation is looked
               up under the "XLA Modules" event that holds it
  program_spans  {name: [count, seconds]} of the `serve.*` host spans
               inside the window
  idle_by_program_span  the device's idle time inside the window, each
               part of each gap under the innermost `serve.*` span that
               covers it, the rest under `outside`; sums to window_s -
               busy_s
  batches      [batch id, seconds under `cmax.engine_pass`] for each
               execution of a scoped module that lies wholly inside the
               window; executions are matched to the batch ids of the
               `serve.dispatch` spans in dispatch order

These keys extend `bench/trace.py`'s reduction, which reads neither and
is left as it is. The readers `bench/metrics/engine.pass_share`,
`engine.slot_pass_us`, `megakernel.prologue_share` and
`service.{launch,harvest}_idle_share` read them from a record's `trace`;
the harness's own reduction does not hold them yet, so in a `bench/run.py`
run they read nothing and are not among `BENCHMARK.json`'s metrics.

As a script it runs one cell traced (`bench/harness.run`, as `bench/run.py
--trace 1 --keep-trace DIR` would), maps each compiled program's
operations to their scopes, and prints the reduction with every
per-layer reading, the new ones included:

    python3 bench/program_trace.py --workload cmax240-mk.backlog \\
        --seed 7 --seconds 30 --out DIR
"""
from __future__ import annotations

import bisect
import re
import time
from collections import Counter, defaultdict
from typing import Dict, List, Tuple

T_PROCESS = time.perf_counter()

SCOPE_PREFIX = "cmax."
SPAN_PREFIX = "serve."
MODULES_LINE = "XLA Modules"
OUTSIDE = "outside"
UNSCOPED = "unscoped"

_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) .*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%([\w.\-]+)")
_NAME = re.compile(r"%([\w.\-]+)")


def scope_chain(op_name: str) -> str:
    """`jit(f)/vmap(g)/cmax.stage1/while/body/cmax.engine_pass/dot` ->
    `cmax.stage1/cmax.engine_pass`; "" where no `cmax.*` scope holds the
    operation."""
    return "/".join(p for p in op_name.split("/")
                    if p.startswith(SCOPE_PREFIX))


def scope_map(hlo_text: str) -> Dict[str, str]:
    """{instruction name: scope chain} of a compiled HLO module's
    instructions that lie under a `cmax.*` scope. An instruction without
    a scope of its own is named by the work it fuses (the most common
    chain among the instructions of the computations it calls: the TPU
    compiler leaves many fusions without an `op_name`), else, where XLA
    made it (no `op_name`, or one that is not JAX's `jit(...)/...` path,
    such as `scatter-add`), by the work that produced its operands."""
    own: Dict[str, str] = {}
    made_by_xla = set()
    calls: Dict[str, List[str]] = {}
    operands: Dict[str, List[str]] = {}
    body: Dict[str, List[str]] = defaultdict(list)
    comp = None
    for line in hlo_text.splitlines():
        if not line[:1].isspace():
            m = _COMPUTATION.match(line)
            comp = m.group(1) if m else None
            continue
        m = _INSTRUCTION.match(line)
        if m is None or comp is None:
            continue
        name = m.group(1)
        op = _OP_NAME.search(line)
        own[name] = scope_chain(op.group(1)) if op else ""
        if op is None or not op.group(1).startswith("jit("):
            made_by_xla.add(name)
        calls[name] = _CALLS.findall(line)
        # every %name after the `=`: its operands, and names of
        # computations, which no instruction shares
        operands[name] = _NAME.findall(line[m.end():])
        body[comp].append(name)

    fused: Dict[str, Counter] = {}

    def fused_chains(name: str) -> Counter:
        if name not in fused:
            c = Counter([own[name]] if own[name] else [])
            for callee in calls[name]:
                for inner in body.get(callee, ()):
                    c.update(fused_chains(inner))
            fused[name] = c
        return fused[name]

    out: Dict[str, str] = {}
    for name in own:               # operands come first in HLO text
        c = fused_chains(name)
        if not c and name in made_by_xla:
            c = Counter(out[o] for o in operands[name] if out.get(o))
        if c:
            out[name] = max(c, key=lambda k: (c[k], len(k), k))
    return out


def module_name(event_name: str) -> str:
    """`jit_estimate_batch_donated(12583891655350763125)` ->
    `jit_estimate_batch_donated`."""
    return event_name.split("(", 1)[0]


def read_program_planes(path: str):
    """(devices: [(ops [(start_ns, end_ns, instruction)],
    modules [(start_ns, end_ns, event name)])], host spans
    [(start_ns, end_ns, name, stats)]) of the `serve.*` spans and the
    window span."""
    from jax.profiler import ProfileData
    from bench import trace
    pd = ProfileData.from_file(path)
    devices, host = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            ops, modules, names = [], [], {}
            for line in plane.lines:
                if line.name not in (trace.OPS_LINE, MODULES_LINE):
                    continue
                for e in line.events:
                    iv = (e.start_ns, e.start_ns + e.duration_ns)
                    if line.name == MODULES_LINE:
                        modules.append(iv + (e.name,))
                        continue
                    if e.name not in names:
                        names[e.name] = trace.short_name(e.name).split()[0]
                    ops.append(iv + (names[e.name],))
            if ops:
                devices.append((ops, modules))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX) \
                            or e.name == trace.WINDOW_SPAN:
                        host.append((e.start_ns, e.start_ns + e.duration_ns,
                                     e.name, dict(e.stats)))
    return devices, host


def self_time_ops(ops) -> List[Tuple[str, object, float]]:
    """(name, tag, self time) of each operation (start, end, name, tag):
    its time less that of the operations nested inside it, as
    `trace.self_times` counts it per name."""
    out: List[list] = []
    stack: list = []          # [end, index into out]
    for a, b, name, tag in sorted(ops, key=lambda o: (o[0], -o[1])):
        while stack and stack[-1][0] <= a:
            stack.pop()
        if stack:
            out[stack[-1][1]][2] -= b - a
        out.append([name, tag, b - a])
        stack.append([b, len(out) - 1])
    return [tuple(o) for o in out]


def innermost_segments(spans) -> List[Tuple[float, float, str]]:
    """Maximal segments of time under one innermost span, for spans that
    nest as `with` blocks on one thread do."""
    out, stack = [], []       # stack: [end, name]
    t = None
    for a, b, n in sorted(spans, key=lambda s: (s[0], -s[1])):
        while stack and stack[-1][0] <= a:
            end, name = stack.pop()
            out.append((t, end, name))
            t = end
        if stack:
            out.append((t, a, stack[-1][1]))
        stack.append([min(b, stack[-1][0]) if stack else b, n])
        t = a
    while stack:
        end, name = stack.pop()
        out.append((t, end, name))
        t = end
    return [s for s in out if s[1] > s[0]]


def apportion(gaps, segments) -> Dict[str, float]:
    """Each gap's time under the labelled segments that overlap it, the
    rest under OUTSIDE; in the time unit of the inputs."""
    out: Dict[str, float] = defaultdict(float)
    starts = [a for a, _, _ in segments]
    for ga, gb in gaps:
        covered = 0.0
        i = max(0, bisect.bisect_right(starts, ga) - 1)
        while i < len(segments) and segments[i][0] < gb:
            a, b, n = segments[i]
            ov = min(b, gb) - max(a, ga)
            if ov > 0:
                out[n] += ov
                covered += ov
            i += 1
        out[OUTSIDE] += (gb - ga) - covered
    return out


def match_batches(executions, dispatch, harvest_end) -> List[int]:
    """The batch id of each execution (sorted by start) of the service's
    program, or None. Batch ids count up in dispatch order and the device
    runs batches in that order, so execution j is batch k + j for one
    offset k: the one under which the most executions start after their
    batch's dispatch (`dispatch`: [(start, id)]) and end before its
    harvest ends (`harvest_end`: {id: end}), where the trace holds
    those. An execution whose batch was not harvested inside the trace
    matches nothing: the trace may have stopped inside it, and its event
    then ends there."""
    start = {b: a for a, b in dispatch}
    seen = set(start) | set(harvest_end)
    if not seen:
        return [None] * len(executions)
    best, best_k = -1, 0
    for k in range(min(seen) - len(executions), max(seen) + 1):
        score = 0
        for j, (a, b) in enumerate(executions):
            score += (k + j in start and start[k + j] <= a) + (
                k + j in harvest_end and b <= harvest_end[k + j])
        if score > best:
            best, best_k = score, k
    return [best_k + j if best_k + j in harvest_end else None
            for j in range(len(executions))]


def reduce_program(devices, host, maps: Dict[str, Dict[str, str]]) -> dict:
    """The keys above, from `read_program_planes` and the scope maps by
    module name (`scope_maps`)."""
    from bench import trace
    win = [(a, b) for a, b, n, _ in host if n == trace.WINDOW_SPAN]
    if len(win) != 1:
        raise RuntimeError(f"expected one {trace.WINDOW_SPAN!r} host span, "
                           f"found {len(win)}")
    lo, hi = win[0]
    spans = [(max(a, lo), min(b, hi), n, st) for a, b, n, st in host
             if n != trace.WINDOW_SPAN and b > lo and a < hi]
    program_spans: Dict[str, list] = {}
    for a, b, n, _ in spans:
        c = program_spans.setdefault(n, [0, 0.0])
        c[0] += 1
        c[1] += (b - a) * 1e-9
    segments = innermost_segments([(a, b, n) for a, b, n, _ in spans])

    dispatch = sorted((a, st["batch"]) for a, _, n, st in spans
                      if n == "serve.dispatch" and "batch" in st)
    harvest_end = {st["batch"]: b for _, b, n, st in spans
                   if n == "serve.harvest" and "batch" in st}

    nd = len(devices)
    scope_s: Dict[str, float] = defaultdict(float)
    idle: Dict[str, float] = defaultdict(float)
    batches: Dict[int, float] = defaultdict(float)
    for ops, modules in devices:
        modules = sorted(modules)
        mstarts = [a for a, _, _ in modules]
        mmaps = [maps.get(module_name(n), {}) for _, _, n in modules]
        per_module: Dict[int, float] = defaultdict(float)
        # tag: the module that ran the operation, found from its start
        clipped = [(max(a, lo), min(b, hi), n,
                    bisect.bisect_right(mstarts, a) - 1) for a, b, n in ops
                   if b > lo and a < hi]
        for n, m, t in self_time_ops(clipped):
            chain = mmaps[m].get(n, "") if m >= 0 else ""
            scope_s[chain or UNSCOPED] += t * 1e-9 / nd
            if "cmax.engine_pass" in chain.split("/"):
                per_module[m] += t * 1e-9 / nd
        execs = [(i, a, b) for i, (a, b, _) in enumerate(modules)
                 if mmaps[i] and lo <= a and b <= hi]
        ids = match_batches([(a, b) for _, a, b in execs], dispatch,
                            harvest_end)
        for (i, _, _), bid in zip(execs, ids):
            if bid is not None:
                batches[bid] += per_module[i]
        iv = [(a, b) for a, b, _, _ in clipped]
        for k, v in apportion(trace.gaps(iv, lo, hi), segments).items():
            idle[k] += v * 1e-9 / nd
    return {
        "scope_s": dict(sorted(scope_s.items(), key=lambda kv: -kv[1])),
        "program_spans": program_spans,
        "idle_by_program_span": dict(idle),
        "batches": sorted([b, t] for b, t in batches.items()),
    }


# ---------------------------------------------------------------------------
# the script: one traced run of a cell, reduced with its programs' scopes
# ---------------------------------------------------------------------------


def compiled_programs(spec) -> List[str]:
    """Compiled HLO text of the programs the cell's service runs: the
    batch function of every batch class its traffic forms, at the served
    shapes (from the persistent cache, which the run filled)."""
    import jax
    import jax.numpy as jnp
    from bench import harness
    from repro.core.pipeline import estimate_batch_donated
    from repro.core.types import EventWindow
    cfg = spec.config
    cmax_cfg = harness.cmax_config(cfg)
    texts = []
    for n in cfg["service"]["length_classes"]:
        for b in harness.batch_classes(spec.mix, cfg["service"]):
            f32 = jax.ShapeDtypeStruct((b, n), jnp.float32)
            ev = EventWindow(f32, f32, f32, f32,
                             jax.ShapeDtypeStruct((b, n), jnp.bool_))
            om = jax.ShapeDtypeStruct((b, 3), jnp.float32)
            texts.append(estimate_batch_donated.lower(ev, om, cmax_cfg)
                         .compile().as_text())
    return texts


def scope_maps(hlo_texts) -> Dict[str, Dict[str, str]]:
    """{module name: scope map} of compiled HLO modules; the maps of
    modules of one name (one program at several batch classes) merge."""
    maps: Dict[str, Dict[str, str]] = {}
    for text in hlo_texts:
        name = text.split(None, 2)[1].rstrip(",")
        maps.setdefault(name, {}).update(scope_map(text))
    return maps


def completion_rate(done) -> float:
    """Windows completed per second after the first completion, as
    `harness.end_to_end` counts them; None below two distinct times."""
    done = sorted(done)
    if len(set(done)) < 2:
        return None
    return sum(1 for t in done if t > done[0]) / (done[-1] - done[0])


def main(argv=None) -> int:
    import argparse
    import json
    import lzma
    import os
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path[0] = root
    sys.path.insert(1, os.path.join(root, "src"))
    from bench import harness, trace

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", required=True, help="directory for the kept "
                    "trace, the scope maps and the reduction")
    args = ap.parse_args(argv)

    spec = harness.cell_spec(args.workload)
    harness.enable_compile_cache(root)
    # `harness.run` keeps the record it hands the readers, and its
    # end-to-end numbers, to itself: wrap the two functions that make them
    records = []
    layer_record = harness.layer_record

    def keep_record(*a, **kw):
        records.append(layer_record(*a, **kw))
        return records[-1]

    harness.layer_record = keep_record
    end_to_end = harness.end_to_end

    def keep_end_to_end(*a, **kw):
        records.append(end_to_end(*a, **kw))
        return records[-1]

    harness.end_to_end = keep_end_to_end
    out = harness.run(spec, args.seed, args.seconds, True, T_PROCESS,
                      keep_trace=args.out)
    e2e, record = records
    texts = compiled_programs(spec)
    for i, text in enumerate(texts):
        with lzma.open(os.path.join(args.out, f"hlo_{i}.txt.xz"), "wt") as f:
            f.write(text)
    maps = scope_maps(texts)
    devices, host = read_program_planes(trace.find_xplane(args.out))
    red = reduce_program(devices, host, maps)
    record["trace"].update(red)
    with open(os.path.join(args.out, "program.json"), "w") as f:
        json.dump({"reduction": record["trace"],
                   "decisions": record["decisions"],
                   "spans": record["spans"]}, f)
    readings = {}
    for name in sorted(os.listdir(os.path.join(harness.BENCH, "metrics"))):
        name = name[:-3]
        readings[name] = harness.reader(name)(record)
    lo, hi = record["traced_s"]
    done = [w["t_done_s"] for w in record["windows"]
            if w["status"] == "ok" and w["t_done_s"] <= args.seconds]
    busy = record["trace"]["busy_s"]
    print(json.dumps({
        "cell": spec.name, "correct": out["correct"], "end_to_end": e2e,
        # windows/s in the window's part before the profiler started and
        # in the profiled part, counted as end_to_end counts them
        "windows_per_s_unprofiled": completion_rate(
            [t for t in done if t < lo]),
        "windows_per_s_profiled": completion_rate(
            [t for t in done if lo <= t <= hi]),
        "readings": readings, "busy_s": busy,
        "window_s": record["trace"]["window_s"],
        "scoped_share": 1 - red["scope_s"].get(UNSCOPED, 0) / busy,
        "module_names": sorted({n for _, mods in devices
                                for _, _, n in mods}),
        **red}), flush=True)
    return 0


if __name__ == "__main__":
    import sys
    sys.exit(main())
