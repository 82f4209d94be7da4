"""The plain reference and the numbers ``correct`` compares."""
import numpy as np
import pytest

from bench import check, gen, reference
from bench.measure import Call
from bench.spec import BENCH


def _afiro(B=1):
    return gen.mps_perturbed({"instance": "data/afiro.mps", "rel": 0.01,
                              "perturb": ["A", "rhs", "c"]}, B,
                             np.random.default_rng(3))


def test_unperturbed_afiro_optimum():
    status, obj = reference.solve(_afiro())      # member 0 is the original
    assert status[0] == "optimal"
    assert obj[0] == pytest.approx(-464.7531428571, abs=1e-6)


def test_dense_by_hand():
    # max 3x + 2y s.t. x + y <= 4, x + 3y <= 6 -> x = 4, y = 0, obj 12
    d = gen.LPData(form="standard", A=np.array([[[1., 1], [1, 3]]]),
                   sense=np.array(["L", "L"]), rhs=np.array([[4., 6]]),
                   lb=np.zeros((1, 2)), ub=np.full((1, 2), np.inf),
                   c=np.array([[3., 2]]), c0=np.zeros(1), maximize=True)
    status, obj = reference.solve(d)
    assert list(status) == ["optimal"] and obj[0] == pytest.approx(12.0)


@pytest.mark.parametrize("shards", [1, 4])
def test_sample_is_drawn_across_shards(shards):
    pairs = check.draw_sample(n_calls=3, batch=400, shards=shards, seed=9,
                              size=64)
    assert len(pairs) == 64 and len(set(pairs)) == 64
    per = np.bincount([i // (400 // shards) for _, i in pairs],
                      minlength=shards)
    assert (per == 64 // shards).all()
    assert pairs == check.draw_sample(3, 400, shards, 9, size=64)


def _answers(d):
    status, obj = reference.solve(d)
    x = np.zeros((d.batch, d.A.shape[2]))
    return {"status": status, "objective": obj, "x": x,
            "iterations": np.zeros(d.batch, np.int32)}


def test_obj_err_reads_one_for_a_wrong_status():
    d = _afiro(4)
    out = _answers(d)
    calls = [Call(0, 0.0, 1.0, out)]
    pairs = [(0, i) for i in range(4)]
    assert check.obj_err([d], calls, pairs) == pytest.approx(0.0, abs=1e-9)
    out["status"] = out["status"].copy()
    out["status"][2] = "iteration_limit"
    assert check.obj_err([d], calls, pairs) == 1.0


def test_full_window_reads_infeasibility_and_gap():
    d = _afiro(2)
    out = _answers(d)
    calls = [Call(0, 0.0, 1.0, out)]
    infeas, gap = check.full_window([d], calls)
    assert infeas > 1e-3           # x = 0 breaks afiro's equality rows
    assert gap > 1e-3              # and its objective is not c.x + c0


def test_verdict_holds_each_number_to_its_limit():
    ok, checks = check.verdict({"obj_err": 1e-7, "x_infeas": 1e-6,
                                "obj_x_gap": 5.0},
                               {"obj_err": 1e-4, "x_infeas": 1e-4})
    assert ok and list(checks) == ["obj_err", "x_infeas"]
    ok, _ = check.verdict({"obj_err": 2e-4, "x_infeas": 0.0},
                          {"obj_err": 1e-4, "x_infeas": 1e-4})
    assert not ok
    ok, _ = check.verdict({"obj_err": np.inf, "x_infeas": 0.0},
                          {"obj_err": 1e-4, "x_infeas": 1e-4})
    assert not ok
    assert (BENCH / "check.py").is_file()
