"""One run of one benchmark cell: set-up, the measured window, the check.

Everything is found by name. A cell of `BENCHMARK.json` names a
configuration, whose file (`bench/configs/<config>.json`) holds the
pipeline, the service settings and the limits of the check, and a traffic
mix (`bench/traffic/<mix>.json`), which `bench/traffic/generate.py` turns
into cameras and arrivals. Each per-layer metric is a reader of its own,
`bench/metrics/<metric>.py`, with `read(record)` returning a number or
None.

A run:
  1. set-up: generate the traffic from the seed, build the service
     (`AsyncBatchedEstimationService`, the entry the program serves
     through), and serve one batch of every batch class this traffic can
     form, so that every program is compiled or loaded from the
     persistent cache; `setup_s` runs from the process's start to the
     first measured submit;
  2. the window: `seconds` of closed-loop or open-loop traffic through
     `submit`/`poll`; with `trace`, its last TRACE_SECONDS under the
     profiler, with host spans `bench.*` around the harness's calls into
     the service;
  3. after it: every window already submitted is served to the end; an
     untraced run then completes the accuracy set (every camera's first
     K windows, K the mix's `accuracy_windows_per_camera`) by serving
     each camera's next windows up to K; the device's peak memory is
     read, and `bench/check.py` holds every served answer, those of the
     completion too, to the plain reference.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import shutil
import statistics
import sys
import tempfile
import time
from typing import Dict, List, Optional

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
POLL_SLEEP_S = 0.0005
#: the traced part of a `--trace 1` run: the last seconds of its window
TRACE_SECONDS = 10.0


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class CellSpec:
    """A cell with everything it names, read from the files."""
    name: str
    config: dict            # the configuration's file
    mix: dict               # the traffic mix's file
    end_to_end: List[dict]  # the cell's end-to-end metric entries
    per_layer: List[dict]   # the cell's per-layer metric entries
    chips: int = 1


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def cell_spec(name: str, root: str = ROOT) -> CellSpec:
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; BENCHMARK.json has "
                         f"{sorted(cells)}")
    cell = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    mix = load_json(os.path.join(BENCH, "traffic", f"{cell['traffic']}.json"))
    return CellSpec(
        name=name, config=load_json(os.path.join(root, conf["file"])),
        mix=mix, chips=int(cell["chips"]),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)])


# ---------------------------------------------------------------------------
# the system under test, built from the configuration's file
# ---------------------------------------------------------------------------


def cmax_config(cfg: dict):
    """The program's `CmaxConfig` as the configuration's file states it."""
    import jax.numpy as jnp
    from repro.core.types import Camera, CmaxConfig, StageConfig
    return CmaxConfig(
        camera=Camera(**cfg["camera"]),
        stages=tuple(StageConfig(**s) for s in cfg["stages"]),
        adaptive=bool(cfg["adaptive"]),
        fixed_iters=tuple(cfg["fixed_iters"]),
        step_size=float(cfg["step_size"]), use_cgpr=bool(cfg["use_cgpr"]),
        dtype=getattr(jnp, cfg["dtype"]), engine=cfg["engine"],
        engine_capacity=int(cfg["engine_capacity"]),
        engine_rb=int(cfg["engine_rb"]))


def recording_workload(cmax_cfg, policy):
    """The program's CMAX workload plugin, unchanged, with host spans
    around batch assembly and harvest, and a record of what each harvested
    batch returned: `results[b]` is batch b's result, and `slots` lists
    (batch, slot) in the order the service hands out responses."""
    import jax
    from repro.serving.workload import CmaxWorkload

    class Recording(CmaxWorkload):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            self.results: list = []
            self.slots: list = []

        def make_batch(self, payloads, states, bucket_n, batch_b):
            with jax.profiler.TraceAnnotation("bench.make_batch"):
                return super().make_batch(payloads, states, bucket_n,
                                          batch_b)

        def harvest(self, result, track_gain):
            with jax.profiler.TraceAnnotation("bench.harvest"):
                slot = super().harvest(result, track_gain)
            b = len(self.results)
            self.results.append(result)

            def recorded(i):
                self.slots.append((b, i))
                return slot(i)
            return recorded

    return Recording(cmax_cfg, policy=policy)


def build_service(cfg: dict, cmax_cfg, trace: bool):
    from repro.data.events import fixed_policy
    from repro.launch.serve import AsyncBatchedEstimationService
    from repro.telemetry import Telemetry
    svc_cfg = cfg["service"]
    workload = recording_workload(cmax_cfg,
                                  fixed_policy(svc_cfg["length_classes"]))
    return AsyncBatchedEstimationService(
        workload=workload, max_batch=int(svc_cfg["max_batch"]),
        max_in_flight=int(svc_cfg["max_in_flight"]),
        telemetry=Telemetry(spans=trace, decisions=trace))


def batch_classes(mix: dict, svc_cfg: dict) -> List[int]:
    """Every batch class the traffic can form. A closed loop whose cameras
    fill max_batch x (max_in_flight + 1) slots in whole batches always
    finds a full batch queued; anything else can form any class up to the
    one its camera count rounds to."""
    mb, depth = int(svc_cfg["max_batch"]), int(svc_cfg["max_in_flight"])
    n = int(mix["cameras"])
    if mix["loop"] == "closed" and n % mb == 0 and n >= mb * (depth + 1):
        return [mb]
    top = min(mb, 1 << max(0, (n - 1).bit_length()))
    return [1 << i for i in range(top.bit_length())]


class CompileCounter:
    """XLA backend compiles, through `jax.monitoring` (as
    `chip_smoke.CompileCounter` counts them)."""

    def __init__(self):
        import jax
        from jax._src import dispatch
        self.compiles = 0
        event = dispatch.BACKEND_COMPILE_EVENT

        def on_duration(name, duration, **_):
            if name == event:
                self.compiles += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)


# ---------------------------------------------------------------------------
# traffic driven through submit / poll
# ---------------------------------------------------------------------------


def event_window(cam_stream, k: int):
    from repro.core.types import EventWindow
    return EventWindow(cam_stream.x[k], cam_stream.y[k], cam_stream.t[k],
                       cam_stream.p[k], cam_stream.valid[k])


@dataclasses.dataclass
class Submitted:
    camera: int
    seq: int
    window: int          # index into the camera's generated windows
    due: float           # host clock (time.monotonic)
    sent: float
    hint: Optional[np.ndarray]


class Client:
    """Submits the traffic's windows and keeps what came back."""

    def __init__(self, svc, traffic):
        self.svc = svc
        self.traffic = traffic
        self.sub: Dict[tuple, Submitted] = {}
        self.next_seq = [0] * len(traffic.cameras)
        self.responses: list = []

    def submit(self, c: int, due: float) -> None:
        import jax
        cam = self.traffic.cameras[c]
        seq = self.next_seq[c]
        self.next_seq[c] += 1
        k = seq % cam.n_windows
        hint = cam.omega_imu[0] if seq == 0 else None
        with jax.profiler.TraceAnnotation("bench.submit"):
            got = self.svc.submit(cam.name, event_window(cam, k),
                                  omega_hint=hint)
        if got != seq:
            raise RuntimeError(f"service numbered {cam.name}'s window {seq} "
                               f"as {got}")
        self.sub[(cam.name, seq)] = Submitted(c, seq, k, due,
                                              time.monotonic(), hint)

    def poll(self) -> list:
        import jax
        with jax.profiler.TraceAnnotation("bench.poll"):
            out = self.svc.poll()
        self.responses.extend(out)
        return out

    def drain(self) -> None:
        self.responses.extend(self.svc.drain())


def idle(until: Optional[float] = None) -> None:
    import jax
    with jax.profiler.TraceAnnotation("bench.sleep"):
        dt = POLL_SLEEP_S if until is None else \
            min(POLL_SLEEP_S, max(0.0, until - time.monotonic()))
        time.sleep(dt)


def warm_up(cfg: dict, cmax_cfg, traffic, classes: List[int]) -> None:
    """Serve one batch of every class in `classes` through a service of
    its own (same programs, clean counters)."""
    svc = build_service(cfg, cmax_cfg, trace=False)
    cams = traffic.cameras
    for b in classes:
        for j in range(b):
            cam = cams[j % len(cams)]
            k = (j // len(cams)) % cam.n_windows
            svc.submit(f"warm{b}-{j}", event_window(cam, k),
                       omega_hint=cam.omega_imu[k])
        got = svc.drain()
        if len(got) != b or any(r.status != "ok" for r in got):
            raise RuntimeError(f"warm-up of batch class {b} failed")


class Profiler:
    """The profiler over the last TRACE_SECONDS of the window (or none):
    `tick` starts it when that part begins; the host span WINDOW_SPAN
    marks the traced part for `bench/trace.py`."""

    def __init__(self, trace_dir: Optional[str], t_start: float):
        self.dir, self.t_start = trace_dir, t_start
        self.span = None
        self.started: Optional[float] = None

    def tick(self, now: float) -> None:
        if self.dir is None or self.span is not None or now < self.t_start:
            return
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0      # host spans are the harness's own
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.span = jax.profiler.TraceAnnotation("bench.window")
        self.span.__enter__()
        self.started = time.monotonic()

    def stop(self) -> None:
        if self.span is not None:
            import jax
            self.span.__exit__(None, None, None)
            jax.profiler.stop_trace()


def run_closed(drv: Client, t0: float, seconds: float, prof: Profiler
               ) -> float:
    t_end = t0 + seconds
    for c in range(len(drv.traffic.cameras)):
        drv.submit(c, t0)
    by_name = {cam.name: c for c, cam in enumerate(drv.traffic.cameras)}
    while True:
        now = time.monotonic()
        if now >= t_end:
            return now
        prof.tick(now)
        got = drv.poll()
        for r in got:
            if time.monotonic() < t_end:
                drv.submit(by_name[r.stream_id], time.monotonic())
        if not got:
            idle()


def complete_set(drv: Client, k: int) -> None:
    """After the window: serve every camera's windows up to sequence
    number k - 1, each submitted when its camera's previous one returns,
    as in the closed loop; cameras that already have k submit nothing."""
    by_name = {cam.name: c for c, cam in enumerate(drv.traffic.cameras)}
    for c in range(len(drv.traffic.cameras)):
        if drv.next_seq[c] < k:
            drv.submit(c, time.monotonic())
    while len(drv.responses) < len(drv.sub):
        got = drv.poll()
        for r in got:
            c = by_name[r.stream_id]
            if drv.next_seq[c] < k:
                drv.submit(c, time.monotonic())
        if not got:
            idle()


def run_open(drv: Client, t0: float, seconds: float, prof: Profiler
             ) -> float:
    tr = drv.traffic
    due = t0 + tr.schedule
    i, n = 0, len(due)
    t_end = t0 + seconds
    while True:
        now = time.monotonic()
        while i < n and due[i] <= now:
            drv.submit(int(tr.schedule_cam[i]), float(due[i]))
            i += 1
        if now >= t_end and i >= n:
            return now
        prof.tick(now)
        got = drv.poll()
        if not got:
            idle(due[i] if i < n else None)


# ---------------------------------------------------------------------------
# end-to-end metrics (host clock)
# ---------------------------------------------------------------------------


def end_to_end(drv: Client, t0: float, seconds: float, setup_s: float
               ) -> dict:
    ok = [r for r in drv.responses if r.status == "ok"]
    out = {"setup_s": setup_s}
    k = int(drv.traffic.mix["accuracy_windows_per_camera"])
    err, fixed = [], []
    for r in ok:
        s = drv.sub[(r.stream_id, r.seq)]
        cam = drv.traffic.cameras[s.camera]
        err.append(np.asarray(r.omega, np.float64) - cam.omega_true[s.window])
        if r.seq < k:
            fixed.append(err[-1])
    if err:
        norm = np.sqrt(np.sum(np.stack(err) ** 2, 1))
        log(f"error to ground truth over {len(norm)} windows: mean "
            f"{norm.mean():.6f}, median {np.median(norm):.6f}, p90 "
            f"{np.percentile(norm, 90):.6f} rad/s")
    # the accuracy set: every camera's first k windows, the same windows
    # for every seed and every count served; read only when all are in
    want = k * len(drv.traffic.cameras)
    if fixed and len(fixed) == want:
        out["rmse_fixed_rad_s"] = float(np.sqrt(np.mean(
            np.sum(np.stack(fixed) ** 2, 1))))
    else:
        log(f"accuracy set: {len(fixed)} of {want} windows answered ok")
    if drv.traffic.schedule is None:
        # closed loop: windows completed per second between the first and
        # the last completion inside the window (completions come a batch
        # at a time, so counting from the window's start would quantise)
        done = sorted(r.t_done for r in ok if r.t_done <= t0 + seconds)
        if len(set(done)) >= 2:
            first = done[0]
            n_after = sum(1 for t in done if t > first)
            out["windows_per_s"] = n_after / (done[-1] - first)
    return out


# ---------------------------------------------------------------------------
# per-layer metric readers
# ---------------------------------------------------------------------------


def reader(name: str):
    path = os.path.join(BENCH, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{len(name)}_{abs(hash(name))}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def layer_record(spec: CellSpec, drv: Client, t0: float, seconds: float,
                 trace_red: Optional[dict], device_kind: str) -> dict:
    from bench import roofline
    svc = drv.svc
    windows = []
    for r in drv.responses:
        s = drv.sub[(r.stream_id, r.seq)]
        cam = drv.traffic.cameras[s.camera]
        windows.append({
            "camera": r.stream_id, "seq": r.seq, "status": r.status,
            "iters": list(r.iters), "t_done_s": r.t_done - t0,
            "due_s": s.due - t0,
            "events": int(cam.valid[s.window].sum())})
    return {
        "cell": spec.name, "config": spec.config, "mix": spec.mix,
        "seconds": seconds,
        "spans": [sp.to_dict() for sp in svc.telemetry.tracer.spans],
        "decisions": list(svc.telemetry.decisions.records),
        "registry": svc.telemetry.registry.snapshot(),
        "trace": trace_red, "windows": windows,
        "peaks": roofline.peaks(device_kind),
    }


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------


def enable_compile_cache(root: str) -> str:
    """JAX's persistent compilation cache, at a fixed path inside the
    checkout; every program is cached, however fast it compiled."""
    path = os.path.join(root, ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    import jax
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def devices_or_exit(chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        log(f"no TPU: JAX found {devs[0].platform!r} devices; the benchmark "
            f"runs only on the chip")
        raise SystemExit(2)
    if len(devs) < chips:
        log(f"the cell asks for {chips} chips, JAX found {len(devs)}")
        raise SystemExit(2)
    return devs


def run(spec: CellSpec, seed: int, seconds: float, trace: bool,
        t_process: float, control: bool = False,
        require_tpu: bool = True, keep_trace: Optional[str] = None) -> dict:
    import jax
    from bench import check as checking
    from bench.traffic.generate import generate

    devs = devices_or_exit(spec.chips) if require_tpu else jax.devices()
    dev = devs[0]
    counter = CompileCounter()
    cfg = spec.config
    t = time.perf_counter()
    traffic = generate(spec.mix, seed, seconds, cfg["camera"])
    n_win = sum(c.n_windows for c in traffic.cameras)
    log(f"[{spec.name}] traffic: {len(traffic.cameras)} cameras, {n_win} "
        f"windows generated in {time.perf_counter() - t:.3f} s")
    cmax_cfg = cmax_config(cfg)
    classes = batch_classes(spec.mix, cfg["service"])
    t = time.perf_counter()
    warm_up(cfg, cmax_cfg, traffic, classes)
    log(f"[{spec.name}] warm-up of batch classes {classes}: "
        f"{time.perf_counter() - t:.3f} s, {counter.compiles} compiles")

    svc = build_service(cfg, cmax_cfg, trace)
    drv = Client(svc, traffic)
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    compiles_before = counter.compiles
    t0 = time.monotonic()
    setup_s = time.perf_counter() - t_process
    prof = Profiler(trace_dir, t0 + max(0.0, seconds - TRACE_SECONDS))
    loop = run_closed if traffic.schedule is None else run_open
    t_stop = loop(drv, t0, seconds, prof)
    prof.stop()
    drv.drain()
    compiles_in_window = counter.compiles - compiles_before
    t_drained = time.monotonic()
    lateness = [s.sent - s.due for s in drv.sub.values()]
    log(f"[{spec.name}] window {t_stop - t0:.3f} s, drained "
        f"{t_drained - t_stop:.3f} s later; {len(drv.sub)} submitted, "
        f"{len(drv.responses)} answered; generator lateness max "
        f"{max(lateness):.6f} s, mean {statistics.fmean(lateness):.6f} s")
    if not trace:
        n_sub = len(drv.sub)
        complete_set(drv, int(spec.mix["accuracy_windows_per_camera"]))
        log(f"[{spec.name}] accuracy set completed: {len(drv.sub) - n_sub} "
            f"more windows in {time.monotonic() - t_drained:.3f} s")
    stats = dev.memory_stats() or {}
    peak = int(stats.get("peak_bytes_in_use", 0))

    trace_red = None
    if trace:
        from bench import trace as tracing
        t = time.perf_counter()
        trace_red = tracing.reduce_trace(trace_dir)
        if keep_trace:
            shutil.copytree(trace_dir, keep_trace, dirs_exist_ok=True)
            with open(os.path.join(keep_trace, "reduced.json"), "w") as f:
                json.dump(trace_red, f, indent=1)
        shutil.rmtree(trace_dir, ignore_errors=True)
        log(f"[{spec.name}] trace reduced in {time.perf_counter() - t:.3f} "
            f"s: busy {trace_red['busy_s']:.6f} s of "
            f"{trace_red['window_s']:.6f} s")

    e2e = end_to_end(drv, t0, seconds, setup_s)
    record = layer_record(spec, drv, t0, seconds, trace_red,
                          dev.device_kind) if trace else None
    if trace:
        record["traced_s"] = [prof.started - t0,
                              prof.started - t0 + trace_red["window_s"]]
    results = jax.device_get(svc.workload.results)
    slots = list(svc.workload.slots)
    del svc, drv.svc
    t = time.perf_counter()
    numbers = checking.check(cfg, traffic, drv, results, slots, seed,
                             compiles_in_window)
    if control:
        numbers.update(checking.control(cfg, traffic, drv, results, slots,
                                        seed))
    log(f"[{spec.name}] check against the reference: "
        f"{time.perf_counter() - t:.3f} s")

    attempted = len(drv.sub)
    failed = attempted - sum(1 for r in drv.responses if r.status == "ok")
    correct = failed == 0 and all(
        n["value"] <= n["limit"] for k, n in numbers.items()
        if not k.startswith("control."))
    if trace:
        metrics = {}
        for m in spec.per_layer:
            v = reader(m["name"])(record)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in spec.end_to_end if m["name"] in e2e}
        missing = [m["name"] for m in spec.end_to_end
                   if m["name"] not in e2e]
        if missing:
            raise RuntimeError(f"no reading of {missing} in this run")
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    out = {"correct": bool(correct), "attempted": attempted,
           "failed": failed, "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = trace_red["busy_s"]
        device["window_s"] = trace_red["window_s"]
        out["breakdown"] = {"device_ops": trace_red["device_ops"],
                            "idle_gaps": trace_red["idle_gaps"]}
    out["checks"] = numbers
    return out
