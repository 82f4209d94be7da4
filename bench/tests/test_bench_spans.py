"""The program's spans, per call: the reduction of ``lp.*`` host events on
synthetic traces (time per span; device idle, call lead and tail by the
innermost open span), and the untraced replay on a small pool run on the
CPU."""
import pytest

from bench import devtrace, gen, spans, spec

MS = 1_000_000  # ns


def _trace():
    # two calls of 10 ms; the device busy 4-6 and 7-8 in call 1, 15-19 in
    # call 2.  Call 1: lp.solve 0.5-9.5 holding h2d 1-3, dispatch 3-4,
    # wait 4-8, d2h 8-9.  Call 2: lp.solve 11-21 holding h2d 12-14,
    # dispatch 14-15, wait 15-19, d2h 19-20.
    calls = [(0, 10 * MS), (11 * MS, 21 * MS)]
    ops = {"/device:TPU:0": [("fusion", 4 * MS, 6 * MS),
                             ("while", 7 * MS, 8 * MS),
                             ("while", 15 * MS, 19 * MS)]}
    events = []
    for t, solve in ((0, (0.5, 9.5)), (11, (11, 21))):
        events += [("lp.solve", solve[0] * MS, solve[1] * MS),
                   ("lp.h2d", (t + 1) * MS, (t + 3) * MS),
                   ("lp.dispatch", (t + 3) * MS, (t + 4) * MS),
                   ("lp.wait", (t + 4) * MS, (t + 8) * MS),
                   ("lp.d2h", (t + 8) * MS, (t + 9) * MS)]
    events.append(("lp.h2d", 30 * MS, 31 * MS))     # outside every call
    return devtrace.from_events(calls, ops), events


def test_span_ms_is_a_mean_per_call():
    t, events = _trace()
    ms = spans.breakdown(t, events)["span_ms"]
    assert ms["lp.h2d"] == pytest.approx(2.0)      # the one outside is left out
    assert ms["lp.d2h"] == pytest.approx(1.0)
    assert ms["lp.solve"] == pytest.approx(9.5)
    assert "lp.canonicalize" not in ms


def test_idle_by_span_names_the_innermost_span():
    t, events = _trace()
    idle = {k: round(v * 1e3, 6)
            for k, v in spans.breakdown(t, events)["idle_by_span"].items()}
    # call 1 idles 0-4, 6-7, 8-10; call 2 idles 11-15, 19-21
    assert idle == {"none": 1.0, "lp.solve": 3.0, "lp.h2d": 4.0,
                    "lp.dispatch": 2.0, "lp.wait": 1.0, "lp.d2h": 2.0}
    assert list(idle)[0] == "lp.h2d"          # the longest first
    # the parts add up to the idle time inside calls
    busy = t.busy_in_calls_s() * 1e3
    assert sum(idle.values()) == pytest.approx(20.0 - busy)


def test_lead_and_tail_by_span_add_up():
    t, events = _trace()
    b = spans.breakdown(t, events)
    # call 1: none 0.5, solve 0.5, h2d 2, dispatch 1; call 2: solve 1,
    # h2d 2, dispatch 1 (the first op starts as lp.wait opens)
    assert b["lead_by_span"] == pytest.approx(
        {"lp.h2d": 2.0, "lp.dispatch": 1.0, "lp.solve": 0.75, "none": 0.25})
    # call 1: d2h 1, solve 0.5, none 0.5; call 2: d2h 1, solve 1
    assert b["tail_by_span"] == pytest.approx(
        {"lp.d2h": 1.0, "lp.solve": 0.75, "none": 0.25})
    lead, tail = t.lead_tail_ms()
    assert sum(b["lead_by_span"].values()) == pytest.approx(lead)
    assert sum(b["tail_by_span"].values()) == pytest.approx(tail)
    assert b["first_op_in"] == {"lp.wait": 2}


def test_no_spans_leave_everything_to_none():
    t, _ = _trace()
    b = spans.breakdown(t, [])
    assert b["span_ms"] == {} and list(b["idle_by_span"]) == ["none"]
    assert b["first_op_in"] == {"none": 2}


@pytest.mark.parametrize("cell,present,absent", [
    ("afiro_b10k", ["lp.canonicalize", "lp.h2d", "lp.d2h", "lp.recover"],
     []),
    ("dense28_b1k_loop", ["lp.h2d", "lp.d2h"],
     ["lp.canonicalize", "lp.recover"]),
])
def test_replay_records_the_program_spans(cell, present, absent):
    c = spec.load_cell(cell)
    pool = gen.make_pool(c.config, {**c.traffic, "pool": 2}, 3, seed=7)
    per_call = spans.replay(c.config, pool)
    assert len(per_call) == 2
    ms = spans.mean_ms(per_call)
    for name in present:
        assert ms[name] > 0, name
    for name in absent:
        assert name not in ms, name
    # a call's children take no longer than the call
    assert all(c["lp.h2d"] + c["lp.d2h"] <= c["lp.solve"] for c in per_call)
