"""Share of the traced window in which the device was idle while the
host formed and dispatched a batch, in %: the idle time under
`serve.launch` and its children `serve.make_batch` and `serve.dispatch`
(`launch/serve.py`) over the window, from the trace's
`idle_by_program_span` (`bench/program_trace.py`). Nothing to read
without that key."""

SPANS = ("serve.launch", "serve.make_batch", "serve.dispatch")


def read(record):
    trace = record.get("trace") or {}
    idle = trace.get("idle_by_program_span")
    if idle is None or trace["window_s"] <= 0:
        return None
    return 100.0 * sum(idle.get(k, 0.0) for k in SPANS) / trace["window_s"]
