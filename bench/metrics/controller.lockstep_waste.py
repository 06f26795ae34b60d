"""Share of the engine passes the device ran that no window needed, in
%: 1 - window passes / slot passes, from the service's counters
(`launch/serve.py`). A batch runs in lockstep (`core/pipeline.py`):
every slot, fill slots included, runs as many passes per stage as the
batch's slowest window, `repro_serving_slot_passes_total`; the windows
needed `repro_serving_window_passes_total`. Nothing to read from a
program without these counters."""


def read(record):
    reg = record["registry"]
    slot = reg.get("repro_serving_slot_passes_total", 0)
    window = reg.get("repro_serving_window_passes_total", 0)
    if not slot:
        return None
    return 100.0 * (1.0 - window / slot)
