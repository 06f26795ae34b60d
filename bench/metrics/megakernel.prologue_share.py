"""Share of the device's busy time in the megakernel's binning prologue,
in %: device self time under the `cmax.bin_taps` scope
(`kernels/ops.py`: warp, tap expansion and the stable sort that packs
each row slab) over busy time, from the trace's `scope_s`
(`bench/program_trace.py`). Nothing to read where the megakernel did not
run."""


def read(record):
    trace = record.get("trace") or {}
    scopes = trace.get("scope_s")
    if not scopes or trace["busy_s"] <= 0:
        return None
    t = sum(v for k, v in scopes.items() if "cmax.bin_taps" in k.split("/"))
    if t <= 0:
        return None
    return 100.0 * t / trace["busy_s"]
