"""Device time of one engine pass of one batch slot, in us: the time
under `cmax.engine_pass` of the batches whose execution lies wholly
inside the traced window (the trace's `batches`,
`bench/program_trace.py`), over their slot passes: batch class x the
passes per stage of the batch's slowest window (its decision records,
`batch` and `iters`), summed over stages. Lockstep runs every slot that
often, so this is the engine's cost per slot and pass, whatever share of
the slots did useful work. Nothing to read without those keys."""


def read(record):
    trace = record.get("trace") or {}
    batches = trace.get("batches")
    if not batches:
        return None
    batch_b = {sp["batch"]: sp["batch_b"] for sp in record["spans"]
               if sp.get("batch") is not None}
    slowest = {}
    for d in record["decisions"]:
        if d.get("batch") is None:
            continue
        key = (d["batch"], d["stage"])
        slowest[key] = max(slowest.get(key, 0), d["iters"] + 1)
    t = passes = 0.0
    for b, seconds in batches:
        per_stage = [v for (bb, _), v in slowest.items() if bb == b]
        if b in batch_b and per_stage:
            t += seconds
            passes += batch_b[b] * sum(per_stage)
    if not passes:
        return None
    return 1e6 * t / passes
