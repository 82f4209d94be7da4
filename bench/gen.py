"""Traffic: batches of LPs made from a seed, as users hand them over.

One general generator serves every configuration: the configuration's
``kind`` picks the law its LPs are drawn from, and the traffic file says
how many LPs a call carries and how many distinct batches the pool holds.
Every batch is float64 NumPy, in the general form the reference solves:

    optimize  c . x + c0   s.t.  lo <= A x <= hi,  lb <= x <= ub

The laws are copies, kept here so that a change to the program cannot
change the traffic: ``dense_standard`` is the paper's Sec. 6 recipe (the
repository's ``core/reference.random_lp_batch``), ``mps_perturbed`` its
Netlib batches (``io/mps.perturbed_batch``).
"""
from __future__ import annotations

import dataclasses
from pathlib import Path
from typing import Optional

import numpy as np

BENCH = Path(__file__).resolve().parent


@dataclasses.dataclass
class LPData:
    """B general-form LPs of one shape.  ``form`` says how the program
    receives them: "standard" (``max c.x, Ax <= b, x >= 0`` as A, b, c) or
    "general" (senses, ranges and bounds, as an MPS file states them)."""

    form: str
    A: np.ndarray            # (B, m, n)
    sense: np.ndarray        # (m,) 'L' | 'G' | 'E'
    rhs: np.ndarray          # (B, m)
    lb: np.ndarray           # (B, n)
    ub: np.ndarray           # (B, n)
    c: np.ndarray            # (B, n)
    c0: np.ndarray           # (B,)
    maximize: bool
    ranges: Optional[np.ndarray] = None   # (m,), NaN where a row has none

    @property
    def batch(self) -> int:
        return self.A.shape[0]

    @property
    def shape(self) -> tuple:
        return self.A.shape[1:]

    def row_bounds(self) -> tuple:
        """(lo, hi), each (B, m): the MPS meaning of senses and ranges."""
        B, m = self.rhs.shape
        lo = np.full((B, m), -np.inf)
        hi = np.full((B, m), np.inf)
        L, G, E = (self.sense == s for s in "LGE")
        lo[:, G | E] = self.rhs[:, G | E]
        hi[:, L | E] = self.rhs[:, L | E]
        if self.ranges is not None:
            R = np.nan_to_num(self.ranges, nan=0.0)
            has = ~np.isnan(self.ranges)
            lo[:, L & has] = self.rhs[:, L & has] - np.abs(R[L & has])
            hi[:, G & has] = self.rhs[:, G & has] + np.abs(R[G & has])
            up, dn = E & has & (R > 0), E & has & (R < 0)
            hi[:, up] = self.rhs[:, up] + R[up]
            lo[:, dn] = self.rhs[:, dn] + R[dn]
        return lo, hi

    def take(self, idx) -> "LPData":
        return dataclasses.replace(
            self, A=self.A[idx], rhs=self.rhs[idx], lb=self.lb[idx],
            ub=self.ub[idx], c=self.c[idx], c0=self.c0[idx])


def dense_standard(cfg: dict, B: int, rng: np.random.Generator) -> LPData:
    """Dense ``max c.x, Ax <= b, x >= 0`` with A, b, c uniform on the
    configuration's ranges (the paper's Sec. 6: A, b in [1, 1000], c in
    [1, 500]); positive A and b make the origin feasible and every LP
    bounded.  Drawn in the order A, c, b, as the paper's generator does."""
    m, n = cfg["m"], cfg["n"]
    A = rng.uniform(*cfg["A_range"], size=(B, m, n))
    c = rng.uniform(*cfg["c_range"], size=(B, n))
    b = rng.uniform(*cfg["b_range"], size=(B, m))
    return LPData(form="standard", A=A, sense=np.full(m, "L"), rhs=b,
                  lb=np.zeros((B, n)), ub=np.full((B, n), np.inf), c=c,
                  c0=np.zeros(B), maximize=True)


def mps_perturbed(cfg: dict, B: int, rng: np.random.Generator) -> LPData:
    """Copies of one MPS instance, each nonzero of the perturbed fields
    multiplied by ``1 + rel * U(-1, 1)``; member 0 is the instance itself.
    Noise is drawn for every entry in the order A, rhs, c, as the paper's
    Netlib batches are built."""
    g = read_mps(BENCH / cfg["instance"])
    rel, on = cfg["rel"], set(cfg["perturb"])

    def expand(arr, field):
        tiled = np.repeat(arr[None], B, axis=0)
        if field in on:
            noise = 1.0 + rel * rng.uniform(-1.0, 1.0, size=tiled.shape)
            noise[0] = 1.0
            tiled = tiled * np.where(tiled != 0.0, noise, 1.0)
        return tiled

    A = expand(g["A"], "A")
    rhs = expand(g["rhs"], "rhs")
    c = expand(g["c"], "c")
    return LPData(form="general", A=A, sense=g["sense"], rhs=rhs,
                  lb=np.repeat(g["lb"][None], B, axis=0),
                  ub=np.repeat(g["ub"][None], B, axis=0), c=c,
                  c0=np.full(B, g["c0"]), maximize=g["maximize"],
                  ranges=g["ranges"])


GENERATORS = {"dense_standard": dense_standard, "mps_perturbed": mps_perturbed}


def make_pool(cfg: dict, traffic: dict, batch: int, seed: int) -> list:
    """``traffic["pool"]`` distinct batches of ``batch`` LPs.

    Every seed gets the same work in another order.  The LPs are one
    population of ``pool * batch``, drawn from the traffic's fixed
    ``population_seed``; batch k holds rows ``k * batch`` to
    ``(k + 1) * batch`` of it.  ``seed`` shuffles the LPs inside each batch
    and the order in which the window takes the batches.  A lockstep batch
    runs as long as its slowest LP, so batches drawn afresh from each seed
    made the work itself differ by a tenth from seed to seed."""
    pool = int(traffic["pool"])
    law = GENERATORS[cfg["kind"]]
    population = law(cfg, pool * batch,
                     np.random.default_rng(int(traffic["population_seed"])))
    rng = np.random.default_rng(seed)
    order = rng.permutation(pool)
    rows = np.concatenate([k * batch + rng.permutation(batch) for k in order])
    dealt = population.take(rows)
    return [dealt.take(slice(k * batch, (k + 1) * batch)) for k in range(pool)]


def read_mps(path) -> dict:
    """A fixed-format MPS file: NAME, OBJSENSE, ROWS, COLUMNS, RHS, RANGES,
    BOUNDS (UP LO FX FR MI PL) and ENDATA.  An RHS entry on the objective
    row sets ``c0 = -value``.  Integer markers are refused: these are LPs."""
    rows, sense, cols = {}, [], {}
    obj = None
    entries, rhs, ranges, bounds = [], {}, {}, []
    maximize = False
    section = None
    with open(path) as f:
        for line in f:
            if not line.strip() or line.startswith("*"):
                continue
            if not line[0].isspace():
                words = line.split()
                section = words[0]
                if section == "OBJSENSE" and len(words) > 1:
                    maximize = words[1].upper().startswith("MAX")
                continue
            w = line.split()
            if section == "OBJSENSE":
                maximize = w[0].upper().startswith("MAX")
            elif section == "ROWS":
                if w[0] == "N":
                    obj = obj or w[1]
                else:
                    rows[w[1]] = len(sense)
                    sense.append(w[0])
            elif section == "COLUMNS":
                if "'MARKER'" in w:
                    raise ValueError(f"{path}: integer markers in an LP")
                cols.setdefault(w[0], len(cols))
                for r, v in zip(w[1::2], w[2::2]):
                    entries.append((r, w[0], float(v)))
            elif section in ("RHS", "RANGES"):
                pairs = w[1:] if len(w) % 2 else w
                for r, v in zip(pairs[0::2], pairs[1::2]):
                    (rhs if section == "RHS" else ranges)[r] = float(v)
            elif section == "BOUNDS":
                bounds.append((w[0], w[2], float(w[3]) if len(w) > 3 else 0.0))
    m, n = len(sense), len(cols)
    A, c = np.zeros((m, n)), np.zeros(n)
    for r, col, v in entries:
        if r == obj:
            c[cols[col]] = v
        elif r in rows:
            A[rows[r], cols[col]] = v
    b, R = np.zeros(m), np.full(m, np.nan)
    for r, v in rhs.items():
        if r in rows:
            b[rows[r]] = v
    for r, v in ranges.items():
        R[rows[r]] = v
    lb, ub = np.zeros(n), np.full(n, np.inf)
    for kind, col, v in bounds:
        j = cols[col]
        if kind == "UP":
            ub[j] = v
        elif kind == "LO":
            lb[j] = v
        elif kind == "FX":
            lb[j] = ub[j] = v
        elif kind == "FR":
            lb[j], ub[j] = -np.inf, np.inf
        elif kind == "MI":
            lb[j] = -np.inf
        elif kind == "PL":
            ub[j] = np.inf
        else:
            raise ValueError(f"{path}: bound type {kind} is not an LP bound")
    return {"A": A, "sense": np.array(sense, dtype="<U1"), "rhs": b,
            "ranges": R if not np.isnan(R).all() else None, "lb": lb,
            "ub": ub, "c": c, "c0": -rhs.get(obj, 0.0), "maximize": maximize}
