"""Share of the device's busy time under the engine pass, in %: device
self time of the operations under the `cmax.engine_pass` scope
(`core/pipeline.py`) over busy time, from the trace's `scope_s`
(`bench/program_trace.py`). Nothing to read without those keys."""


def read(record):
    trace = record.get("trace") or {}
    scopes = trace.get("scope_s")
    if not scopes or trace["busy_s"] <= 0:
        return None
    t = sum(v for k, v in scopes.items()
            if "cmax.engine_pass" in k.split("/"))
    return 100.0 * t / trace["busy_s"]
