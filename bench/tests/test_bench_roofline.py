"""The roofline arithmetic against hand counts, and the peaks table."""
import numpy as np
import pytest

from bench import devtrace, gen, measure, roofline, spec


def test_ops_per_iteration():
    assert roofline.ops_per_iteration(28, 28) == 2 * 29 * 29 == 1682
    assert roofline.ops_per_iteration(27, 32) == 2 * 28 * 33


def test_bytes_per_lp_dense():
    d = gen.dense_standard({"m": 28, "n": 28, "A_range": [1, 2],
                            "b_range": [1, 2], "c_range": [1, 2]},
                           2, np.random.default_rng(0))
    # A, b, c in f32 (no bounds), x and objective in f32, status int8
    assert roofline.bytes_per_lp(d) == 4 * (28 * 28 + 28 + 28) + 4 * 28 + 4 + 1


def test_bytes_per_lp_counts_bounds_only_where_present():
    d = gen.mps_perturbed({"instance": "data/afiro.mps", "rel": 0.01,
                           "perturb": ["A"]}, 2, np.random.default_rng(0))
    base = 4 * (27 * 32 + 27 + 32) + 4 * 32 + 5
    assert roofline.bytes_per_lp(d) == base
    d.ub = np.full_like(d.ub, 3.0)
    assert roofline.bytes_per_lp(d) == base + 4 * 32


class _Dev:
    device_kind = "TPU v5 lite"


def _run(iterations, busy_ns, chips=1):
    d = gen.dense_standard({"m": 28, "n": 28, "A_range": [1, 2],
                            "b_range": [1, 2], "c_range": [1, 2]},
                           len(iterations), np.random.default_rng(0))
    call = measure.Call(0, 0.0, 1.0, {"iterations": np.asarray(iterations)})
    ops = {f"/device:TPU:{k}": [("op", 0, busy_ns)] for k in range(chips)}
    trace = devtrace.from_events([(0, max(busy_ns, 1) * 2)], ops)
    return measure.Run(cell=None, data=[d], calls=[call], setup_s=0.0,
                       devices=[_Dev()] * chips, memory=[], trace=trace)


def test_least_time_hand_count():
    run = _run([10, 20], busy_ns=1_000_000)
    t, bound = roofline.least_time_s(run)
    ops = 30 * 1682
    bytes_ = 2 * (4 * 840 + 4 * 28 + 5)
    assert bound == "memory"
    assert t == pytest.approx(max(ops / 197e12, bytes_ / 819e9))


def test_least_time_counts_the_traced_calls_only():
    run = _run([10, 20], busy_ns=1_000_000)
    one = roofline.least_time_s(run)[0]
    later = measure.Call(0, 1.0, 2.0, {"iterations": np.array([10, 20])})
    run.calls.append(later)
    assert roofline.least_time_s(run)[0] == pytest.approx(2 * one)
    run.traced = 1
    assert roofline.least_time_s(run)[0] == pytest.approx(one)


@pytest.mark.parametrize("chips", [1, 4])
def test_share_is_100_when_busy_is_the_least_time(chips):
    its = [10_000_000, 3]      # enough pivots to be compute-bound
    t, bound = roofline.least_time_s(_run(its, 1, chips))
    assert bound == "compute"
    run = _run(its, busy_ns=t * 1e9, chips=chips)
    assert roofline.share_pct(run) == pytest.approx(100.0, rel=1e-6)
    slower = _run(its, busy_ns=t * 2e9, chips=chips)
    assert roofline.share_pct(slower) == pytest.approx(50.0, rel=1e-6)


def test_unknown_device_kind_is_an_error():
    assert measure.load_peaks("TPU v5 lite")["flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        measure.load_peaks("TPU v4")


@pytest.mark.parametrize("stats,want", [
    # the fullest device counts, reserved program space with the buffers
    ([{"peak_bytes_in_use": 100, "peak_bytes_reserved": 300, "bytes_limit": 1000},
      {"peak_bytes_in_use": 50, "bytes_limit": 1000}], 40.0),
    ([{"peak_bytes_in_use": 100, "bytes_limit": 1000}], 10.0),
    ([{"peak_bytes_in_use": 100}], None),          # no limit: nothing to read
])
def test_peak_hbm_counts_reserved_program_space(stats, want):
    run = _run([1], busy_ns=1)
    run.memory = stats
    got = spec.metric_reader("peak_hbm_pct.batch")(run)
    assert got == (None if want is None else pytest.approx(want))
