"""How ``correct`` is decided: the answers of the timed calls against the
reference and against the LP data itself.

Three numbers; a configuration's ``limits`` name those it is held to, each
with its limit (PERF.md gives the readings each was set from):

* ``obj_err`` -- over a sample of the window's answers drawn from the
  seed, ``|obj - ref| / max(1, |ref|)`` against the HiGHS reference; an
  answer whose status differs from the reference's reads 1.
* ``x_infeas`` -- over every answer the program calls optimal, the largest
  violation of a row or a bound by its x, relative to the row's magnitude
  ``max(1, sum |a_ij x_j|, |lo|, |hi|)`` or the bound's ``max(1, |bound|)``.
* ``obj_x_gap`` -- over the same answers, ``|c.x + c0 - obj| / max(1,
  |obj|)``: the objective reported is the objective of the x reported.

Together they say that each x is feasible, that its objective is the one
reported, and (on the sample) that it is optimal.
"""
from __future__ import annotations

import numpy as np

from bench import reference

SAMPLE = 512      # answers compared with the reference per run
BLOCK = 25_000    # LPs per block of the full-window checks (host memory)
NAMES = ("obj_err", "x_infeas", "obj_x_gap")


def draw_sample(n_calls: int, batch: int, shards: int, seed: int,
                size: int = SAMPLE) -> list:
    """(call, LP) pairs drawn from the seed, the same number from each of
    the ``shards`` equal, contiguous parts of a batch (one per chip)."""
    rng = np.random.default_rng([seed, 7919])
    per = -(-min(size, n_calls * batch) // shards)
    width = batch // shards
    picks = set()
    for s in range(shards):
        cells = n_calls * width
        for flat in rng.choice(cells, size=min(per, cells), replace=False):
            call, i = divmod(int(flat), width)
            picks.add((call, s * width + i))
    return sorted(picks)


def obj_err(pool: list, calls: list, pairs: list) -> float:
    """Largest relative objective error of the sampled answers."""
    worst = 0.0
    by_batch = {}
    for call, i in pairs:
        by_batch.setdefault(calls[call].pool_index, set()).add(i)
    refs = {}
    for p, idx in by_batch.items():
        idx = np.array(sorted(idx))
        status, obj = reference.solve(pool[p].take(idx))
        refs.update({(p, int(i)): (s, o) for i, s, o in zip(idx, status, obj)})
    for call, i in pairs:
        got = calls[call].out
        s_ref, o_ref = refs[(calls[call].pool_index, i)]
        if got["status"][i] != s_ref:
            worst = max(worst, 1.0)
        elif s_ref == "optimal":
            o = float(got["objective"][i])
            worst = max(worst, abs(o - o_ref) / max(1.0, abs(o_ref))
                        if np.isfinite(o) else 1.0)
    return worst


def full_window(pool: list, calls: list) -> tuple:
    """(x_infeas, obj_x_gap) over every answer called optimal."""
    infeas = gap = 0.0
    for call in calls:
        data, out = pool[call.pool_index], call.out
        for s in range(0, data.batch, BLOCK):
            e = min(s + BLOCK, data.batch)
            ok = out["status"][s:e] == "optimal"
            if not ok.any():
                continue
            d = data.take(np.arange(s, e)[ok])
            x = out["x"][s:e][ok].astype(np.float64)
            obj = out["objective"][s:e][ok].astype(np.float64)
            if not np.isfinite(x).all() or not np.isfinite(obj).all():
                return np.inf, np.inf
            lo, hi = d.row_bounds()
            ax = np.matmul(d.A, x[:, :, None])[:, :, 0]
            mag = np.matmul(np.abs(d.A), np.abs(x)[:, :, None])[:, :, 0]
            with np.errstate(invalid="ignore"):
                row = np.maximum(np.nan_to_num(lo - ax, nan=0.0, neginf=0.0),
                                 np.nan_to_num(ax - hi, nan=0.0, neginf=0.0))
                scale = np.maximum.reduce([
                    np.ones_like(mag), mag,
                    np.abs(np.where(np.isfinite(lo), lo, 0.0)),
                    np.abs(np.where(np.isfinite(hi), hi, 0.0))])
                bnd = np.maximum(d.lb - x, x - d.ub)
                bscale = np.maximum(1.0, np.maximum(
                    np.abs(np.where(np.isfinite(d.lb), d.lb, 0.0)),
                    np.abs(np.where(np.isfinite(d.ub), d.ub, 0.0))))
            infeas = max(infeas, float((row / scale).max(initial=0.0)),
                         float((np.maximum(bnd, 0.0) / bscale).max(initial=0.0)))
            cx = np.einsum("bn,bn->b", d.c, x) + d.c0
            gap = max(gap, float((np.abs(cx - obj)
                                  / np.maximum(1.0, np.abs(obj))).max()))
    return infeas, gap


def compare(pool: list, calls: list, seed: int, shards: int) -> dict:
    """Every number ``correct`` compares, by name."""
    pairs = draw_sample(len(calls), pool[0].batch, shards, seed)
    infeas, gap = full_window(pool, calls)
    return {"obj_err": obj_err(pool, calls, pairs), "x_infeas": infeas,
            "obj_x_gap": gap}


def verdict(numbers: dict, limits: dict) -> tuple:
    """(correct, {name: {"value", "limit"}}) over the numbers the
    configuration has limits for, in the order of NAMES."""
    checks = {k: {"value": float(numbers[k]), "limit": limits[k]}
              for k in NAMES if k in limits}
    ok = all(np.isfinite(v["value"]) and v["value"] <= v["limit"]
             for v in checks.values())
    return ok, checks
