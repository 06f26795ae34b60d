"""Share of the device's busy time that the engine passes of the windows
served in the traced part of the window would need at the chip's peaks,
in %.

The work is the algorithm's own count (`bench/roofline.py`): each window's
real events after the stage's subsampling and the passes the decision log
records, whichever engine ran them. Busy time is the union of device
operations in the trace. Nothing to read without a trace or a served
window."""
from bench import roofline


def read(record):
    trace = record.get("trace")
    if not trace or trace["busy_s"] <= 0:
        return None
    iters = {}
    for d in record["decisions"]:
        iters.setdefault((d["stream_id"], d["seq"]), {})[d["stage"]] = \
            d["iters"]
    cfg = record["config"]
    flops = nbytes = 0.0
    for w in record["windows"]:
        its = iters.get((w["camera"], w["seq"]))
        lo, hi = record["traced_s"]
        if w["status"] != "ok" or its is None \
                or not lo <= w["t_done_s"] <= hi:
            continue
        passes = [its[s] + 1 for s in range(len(cfg["stages"]))]
        f, b = roofline.window_work(w["events"], passes, cfg["camera"],
                                    cfg["stages"])
        flops += f
        nbytes += b
    if flops == 0:
        return None
    least, _ = roofline.least_time_s(flops, nbytes, record["peaks"])
    return 100.0 * least / trace["busy_s"]
