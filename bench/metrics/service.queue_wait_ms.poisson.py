"""Mean queue wait of the windows due in the window, in ms: the span
phase `queue_wait` (submit to admission into a batch) of the service
(`launch/serve.py`), on the service's own clock."""


def read(record):
    waits = [sp["phases"]["queue_wait"] for sp in record["spans"]
             if sp["status"] == "ok" and "queue_wait" in sp["phases"]]
    if not waits:
        return None
    return 1e3 * sum(waits) / len(waits)
