"""Share of the traced window in which the device was idle while the
host harvested a finished batch, in %: the idle time under
`serve.harvest` (`launch/serve.py`: the wait for the result, its copy to
the host, responses and decision records) over the window, from the
trace's `idle_by_program_span` (`bench/program_trace.py`). Nothing to
read without that key."""


def read(record):
    trace = record.get("trace") or {}
    idle = trace.get("idle_by_program_span")
    if idle is None or trace["window_s"] <= 0:
        return None
    return 100.0 * idle.get("serve.harvest", 0.0) / trace["window_s"]
