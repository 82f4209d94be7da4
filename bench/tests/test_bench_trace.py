"""Trace reduction on synthetic events: busy union, idle gaps, per-call
lead and tail, per-device imbalance, and the breakdown's names."""
import pytest

from bench import devtrace

MS = 1_000_000  # ns


def test_merge_and_gaps():
    merged = devtrace.merge([(5, 7), (0, 2), (1, 3), (7, 8)])
    assert merged == [[0, 3], [5, 8]]
    assert devtrace.gaps(merged, 0, 10) == [(3, 5), (8, 10)]
    assert devtrace.gaps(merged, 1, 6) == [(3, 5)]


@pytest.mark.parametrize("lo,hi,want", [(0, 10, 6), (1, 6, 3), (3, 5, 0),
                                        (-5, 100, 6), (2, 9, 4)])
def test_busy_covered(lo, hi, want):
    busy = devtrace.Busy(devtrace.merge([(0, 3), (5, 8)]))
    assert busy.covered(lo, hi) == want


def _trace():
    # two calls of 10 ms; device a busy 2-6 and 7-8 in call 1, 12-18 in
    # call 2; device b busy 3-5 in call 1 and 13-14 in call 2.
    calls = [(0, 10 * MS), (11 * MS, 21 * MS)]
    ops = {"/device:TPU:0": [("fusion", 2 * MS, 6 * MS),
                             ("while", 7 * MS, 8 * MS),
                             ("while", 12 * MS, 18 * MS)],
           "/device:TPU:1": [("fusion", 3 * MS, 5 * MS),
                             ("while", 13 * MS, 14 * MS)]}
    return devtrace.from_events(calls, ops)


def test_busy_and_idle_share():
    t = _trace()
    assert t.window_s() == pytest.approx(0.021)
    # a: 4 + 1 + 6 = 11 ms; b: 2 + 1 = 3 ms; mean 7 ms of 21
    assert t.busy_s() == pytest.approx(0.007)
    assert t.idle_share() == pytest.approx(1 - 7 / 21)
    assert t.busy_in_calls_s() == pytest.approx(0.007)


def test_lead_and_tail():
    lead, tail = _trace().lead_tail_ms()
    # call 1: first op at 2, last ends at 8 -> lead 2, tail 2
    # call 2: first op at 12, last ends at 18 -> lead 1, tail 3
    assert lead == pytest.approx(1.5)
    assert tail == pytest.approx(2.5)


def test_imbalance():
    # call 1: a 5 ms, b 2 ms -> (5 - 3.5) / 5; call 2: a 6, b 1 -> 2.5 / 6
    want = ((5 - 3.5) / 5 + (6 - 3.5) / 6) / 2
    assert _trace().imbalance() == pytest.approx(want)


def test_no_device_ops_reads_nothing():
    t = devtrace.from_events([(0, MS)], {"/device:TPU:0": []})
    assert t.lead_tail_ms() == (None, None)
    assert t.imbalance() is None


def test_breakdown_names_gaps_by_host():
    b = _trace().breakdown()
    ops = dict(b["device_ops"])
    assert ops["while"] == pytest.approx((1 + 6 + 1) * MS / 2 / 1e9)
    assert ops["fusion"] == pytest.approx((4 + 2) * MS / 2 / 1e9)
    gaps = sorted((name, round(s * 1e3, 6)) for name, s in b["idle_gaps"])
    assert gaps == sorted([("in_call.lead", 2.0), ("in_call.mid", 1.0),
                           ("in_call.tail", 2.0), ("between_calls", 1.0),
                           ("in_call.lead", 1.0), ("in_call.tail", 3.0)])
    assert all(len(v) <= 10 for v in b.values())


@pytest.mark.parametrize("op,want", [
    ("%fusion.278 = f32[100000,29]{0,1:T(8,128)S(1)} fusion(f32[100000,29,57]"
     "{0,1,2:T(8,128)} %get-tuple-element.2107), kind=kCustom", "fusion.278 (fusion)"),
    ("%while.49 = (f32[100000,29,57]{0,1,2:T(8,128)}, s32[100000]{0:T(1024)})"
     " while((f32[100000,29,57]{0,1,2:T(8,128)}, s32[100000]) %tuple.1)",
     "while.49 (while)"),
    ("%sort.5 = (s32[2900000]{0:T(1024)S(1)}, f32[2900000]{0:T(1024)S(1)}) "
     "sort(s32[2900000]{0:T(1024)S(1)} %reshape.1308)", "sort.5 (sort)"),
    ("jit_solve", "jit_solve"),
])
def test_short_op_names(op, want):
    assert devtrace.short_name(op) == want
