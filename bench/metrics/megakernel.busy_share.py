"""Share of the device's busy time spent in Mosaic (Pallas) kernels, in %:
the megakernel (`kernels/megakernel.py`) on the served path. From the
trace's custom-call operations; nothing to read where none ran."""


def read(record):
    trace = record.get("trace")
    if not trace or trace["busy_s"] <= 0 or trace["custom_call_s"] <= 0:
        return None
    return 100.0 * trace["custom_call_s"] / trace["busy_s"]
