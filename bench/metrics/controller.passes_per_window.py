"""Engine passes per served window: at every stage, the entry pass plus
one per controller iteration (`core/pipeline.py`, `core/adaptive.py`),
summed over stages and averaged over windows, from the decision log."""


def read(record):
    per_window = {}
    for d in record["decisions"]:
        key = (d["stream_id"], d["seq"])
        per_window[key] = per_window.get(key, 0) + d["iters"] + 1
    if not per_window:
        return None
    return sum(per_window.values()) / len(per_window)
