"""Per-layer readers, and BENCHMARK.json's metrics against its cells."""
import json
import math
import os

import pytest

from bench import harness

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def bench_file():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def window(due, done, status="ok"):
    return {"due_s": due, "t_done_s": done, "status": status}


def test_p95_latency_reads_every_window_from_its_due_time():
    read = harness.reader("service.p95_latency_ms.poisson")
    # latencies 1..100 ms: numpy's linear p95 is 95.05 ms
    rec = {"windows": [window(1.0, 1.0 + k / 1e3) for k in range(1, 101)]}
    assert read(rec) == pytest.approx(95.05)
    assert read({"windows": []}) is None


def test_p95_latency_counts_an_unanswered_window_as_infinite():
    read = harness.reader("service.p95_latency_ms.poisson")
    wins = [window(0.0, 0.001) for _ in range(10)]
    wins += [window(0.0, 0.0, status="failed")] * 2
    assert math.isinf(read({"windows": wins}))


def test_every_per_layer_metric_has_a_reader_and_moves_its_cells():
    bench = bench_file()
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    cells = [w["name"] for w in bench["workloads"]]
    for m in bench["per_layer"]:
        assert callable(harness.reader(m["name"]))
        moved = e2e[m["moves"]]
        for cell in m.get("workloads", cells):
            assert cell in cells
            assert "workloads" not in moved or cell in moved["workloads"]


def test_every_cell_reports_setup_and_another_end_to_end_metric():
    bench = bench_file()
    for w in bench["workloads"]:
        spec = harness.cell_spec(w["name"], ROOT)
        names = {m["name"] for m in spec.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert spec.per_layer


def test_every_cell_reports_accuracy_on_its_fixed_set():
    bench = bench_file()
    assert "rmse_rad_s" not in {m["name"] for m in bench["end_to_end"]}
    for w in bench["workloads"]:
        spec = harness.cell_spec(w["name"], ROOT)
        assert "rmse_fixed_rad_s" in {m["name"] for m in spec.end_to_end}
        assert spec.mix["accuracy_windows_per_camera"] >= 1
