"""The benchmark's copies of the traffic laws and of the MPS reader draw
exactly what the repository's originals draw."""
import numpy as np
import pytest

from bench import gen
from bench.spec import BENCH, load_benchmark, load_cell
from repro.configs.paper_lp import WORKLOADS, build_batch
from repro.io.mps import fixture_path, perturbed_batch, read_mps


def test_afiro_copy_is_the_fixture():
    assert (BENCH / "data" / "afiro.mps").read_bytes() == \
        open(fixture_path("afiro"), "rb").read()


def test_read_mps_matches_program_reader():
    mine = gen.read_mps(BENCH / "data" / "afiro.mps")
    theirs = read_mps(fixture_path("afiro"))
    np.testing.assert_array_equal(mine["A"], theirs.A[0])
    np.testing.assert_array_equal(mine["rhs"], theirs.rhs[0])
    np.testing.assert_array_equal(mine["c"], theirs.c[0])
    np.testing.assert_array_equal(mine["lb"], theirs.lb[0])
    np.testing.assert_array_equal(mine["ub"], theirs.ub[0])
    np.testing.assert_array_equal(mine["sense"], theirs.sense)
    assert mine["c0"] == theirs.c0[0] and mine["maximize"] == theirs.maximize


DENSE = [w["name"] for w in load_benchmark()["workloads"]
         if load_cell(w["name"]).config["kind"] == "dense_standard"]


@pytest.mark.parametrize("cell", DENSE)
def test_dense_law_matches_build_batch(cell):
    cfg = load_cell(cell).config
    want = build_batch({w.name: w for w in WORKLOADS}["lp_28d_100k"],
                       batch=6, rng=np.random.default_rng(11))
    got = gen.dense_standard(cfg, 6, np.random.default_rng(11))
    np.testing.assert_array_equal(got.A, want.A)
    np.testing.assert_array_equal(got.rhs, want.b)
    np.testing.assert_array_equal(got.c, want.c)


def test_afiro_law_matches_perturbed_batch():
    cfg = load_cell("afiro_b10k").config
    want = perturbed_batch(read_mps(fixture_path("afiro")), 6,
                           np.random.default_rng(11))
    got = gen.mps_perturbed(cfg, 6, np.random.default_rng(11))
    for f in ("A", "rhs", "c", "lb", "ub", "c0"):
        np.testing.assert_array_equal(getattr(got, f), getattr(want, f))
    np.testing.assert_array_equal(got.sense, want.sense)
    np.testing.assert_array_equal(got.A[0], got.A[0] * (got.A[0] != 0))
    assert got.maximize == want.maximize


def test_pool_is_fixed_by_the_seed():
    cell = load_cell("dense28_b1k_loop")
    traffic = {**cell.traffic, "pool": 3}
    seed = 2**31 + 12345            # seeds exceed 32 signed bits
    a = gen.make_pool(cell.config, traffic, 4, seed)
    b = gen.make_pool(cell.config, traffic, 4, seed)
    c = gen.make_pool(cell.config, traffic, 4, seed + 1)
    assert len(a) == 3 and all(x.batch == 4 for x in a)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x.A, y.A)
    assert not np.array_equal(a[0].A, a[1].A)       # distinct batches
    assert not np.array_equal(a[0].A, c[0].A)
    # every seed deals the same population of LPs, so the same work
    rows = lambda pool: np.sort(np.concatenate([x.c for x in pool])[:, 0])  # noqa: E731
    np.testing.assert_array_equal(rows(a), rows(c))
    # and each batch keeps its LPs: only their order inside it changes
    batches = lambda pool: sorted(tuple(np.sort(x.c[:, 0])) for x in pool)  # noqa: E731
    assert batches(a) == batches(c)


def test_row_bounds_follow_mps_ranges():
    d = gen.LPData(form="general", A=np.zeros((1, 4, 1)),
                   sense=np.array(list("LGEE")), rhs=np.array([[5., 5, 5, 5]]),
                   lb=np.zeros((1, 1)), ub=np.ones((1, 1)), c=np.zeros((1, 1)),
                   c0=np.zeros(1), maximize=False,
                   ranges=np.array([2., 3, 4, -4]))
    lo, hi = d.row_bounds()
    np.testing.assert_array_equal(lo, [[3, 5, 5, 1]])
    np.testing.assert_array_equal(hi, [[5, 8, 9, 5]])
