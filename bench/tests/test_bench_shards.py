"""The four-chip cell: its configuration draws the one-chip dense law, and
its imbalance reader reads a synthetic four-chip trace."""
import json
import types

import numpy as np
import pytest

from bench import devtrace, gen, spec

MS = 1_000_000  # ns
CELL = "dense28x4_b400k"
BATCH_METRICS = {"host_lead_ms.batch", "host_tail_ms.batch",
                 "iters_per_lp.batch", "solve_roofline_pct.batch",
                 "device_idle_pct.batch", "peak_hbm_pct.batch"}


def _config(name: str) -> dict:
    entry = {c["name"]: c for c in spec.load_benchmark()["configs"]}[name]
    return json.loads((spec.ROOT / entry["file"]).read_text())


def test_x4_config_is_the_dense_law_at_four_chips():
    one, four = _config("paper_dense_28"), _config("paper_dense_28_x4")
    # the statements of source and deployment differ, and a note says
    # which reference the configuration shares; everything read is equal
    # but the batch and the entry point
    prose = {"source", "deployment", "reference_note"}
    differ = {k for k in (one.keys() | four.keys()) - prose
              if one.get(k) != four.get(k)}
    assert differ == {"batch", "entry"}
    assert four["batch"] == 4 * one["batch"] and four["reduced"] == []
    assert four["entry"] == "solve_shard_map"
    a = gen.dense_standard(one, 8, np.random.default_rng(5))
    b = gen.dense_standard(four, 8, np.random.default_rng(5))
    for f in ("A", "rhs", "c", "lb", "ub"):
        np.testing.assert_array_equal(getattr(a, f), getattr(b, f))


def test_x4_cell_reports_the_batch_metrics_and_imbalance():
    cell = spec.load_cell(CELL)
    assert cell.chips == 4 and cell.batch == 400_000
    assert cell.traffic["pool"] == 2
    assert {m["name"] for m in cell.end_to_end} == {"solves_per_s", "setup_s"}
    assert {m["name"] for m in cell.per_layer} == \
        BATCH_METRICS | {"shard_imbalance_pct.batch"}
    # the one-chip cells do not read the four-chip metric
    for other in ("dense28_b100k", "afiro_b10k", "dense28_b1k_loop"):
        assert "shard_imbalance_pct.batch" not in \
            {m["name"] for m in spec.load_cell(other).per_layer}


def _read(trace):
    return spec.metric_reader("shard_imbalance_pct.batch")(
        types.SimpleNamespace(trace=trace))


def test_imbalance_reader_on_four_chips():
    # two calls of 10 ms; in call 1 the chips are busy 8, 8, 8 and 4 ms,
    # in call 2 all four 6 ms
    calls = [(0, 10 * MS), (20 * MS, 30 * MS)]
    ops = {f"/device:TPU:{k}": [("while", 1 * MS, (1 + busy) * MS),
                                ("while", 22 * MS, 28 * MS)]
           for k, busy in enumerate((8, 8, 8, 4))}
    want = 100.0 * ((8 - 7) / 8 + 0.0) / 2
    assert _read(devtrace.from_events(calls, ops)) == pytest.approx(want)


def test_imbalance_reader_without_device_ops():
    ops = {f"/device:TPU:{k}": [] for k in range(4)}
    assert _read(devtrace.from_events([(0, MS)], ops)) is None
