"""Share of the event slots dispatched that held no real event: length
padding plus batch-fill slots (`serving/workload.py` fills a part-full
batch class by repeating its leader). From the registry's counters
`repro_serving_event_slots_total` and `repro_serving_raw_events_total`."""


def read(record):
    reg = record["registry"]
    slots = reg.get("repro_serving_event_slots_total", 0)
    raw = reg.get("repro_serving_raw_events_total", 0)
    if not slots:
        return None
    return (slots - raw) / slots
