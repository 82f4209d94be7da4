import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Dry-run of the paper's own workload on the production mesh: batches of
LPs sharded over all 256/512 chips, as ``solve_shard_map`` runs them:
per-chip termination (each chip its own while loop, the TPU analogue of
the paper's per-block early exit; zero cross-chip collectives).

The simplex while-loop has no static trip count, so the HLO cost model takes
default_trip = the oracle-measured mean pivot count for the workload class.

  PYTHONPATH=src python -m repro.launch.dryrun_lp [--multi-pod]
"""
import argparse
import json

import numpy as np


def main():
    import jax
    from repro.configs.paper_lp import WORKLOADS, build_batch
    from repro.core import LPBatch, canonical_shape, solve_batched_reference
    from repro.core.distributed import solve_shard_map
    from repro.launch.mesh import make_production_mesh
    from repro.analysis.hlo_cost import module_cost
    from repro.core.simplex import flops_per_pivot

    ap = argparse.ArgumentParser()
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--out", default="artifacts/dryrun_lp")
    args = ap.parse_args()

    mesh = make_production_mesh(multi_pod=args.multi_pod)
    chips = int(np.prod(mesh.devices.shape))
    mesh_name = "x".join(str(s) for s in mesh.devices.shape)
    os.makedirs(args.out, exist_ok=True)
    rng = np.random.default_rng(0)

    for wl in WORKLOADS:
        # measure typical pivot counts on a small oracle sample (the oracle
        # accepts fixture-backed GeneralLPBatch samples directly)
        sample = build_batch(wl, batch=32, rng=rng)
        ref = solve_batched_reference(sample)
        mean_pivots = float(ref.iterations.mean())

        # fixture workloads are lowered at their *canonical* shape — that is
        # the tableau geometry the chips actually execute
        m_dev, n_dev = ((wl.m, wl.n) if wl.fixture is None
                        else canonical_shape(sample))
        batch = LPBatch(
            A=np.zeros((wl.batch, m_dev, n_dev), np.float32),
            b=np.zeros((wl.batch, m_dev), np.float32),
            c=np.zeros((wl.batch, n_dev), np.float32))
        rec = {"workload": wl.name, "mesh": mesh_name, "chips": chips,
               "batch": wl.batch, "m": wl.m, "n": wl.n,
               "m_device": m_dev, "n_device": n_dev,
               "mean_pivots": mean_pivots}
        mode = "shard_map"
        with mesh:
            lowered = solve_shard_map(batch, mesh, lower_only=True)
            compiled = lowered.compile()
        txt = compiled.as_text()
        cost = module_cost(txt, default_trip=mean_pivots)
        ana = flops_per_pivot(m_dev, n_dev) * mean_pivots * wl.batch / chips
        mem = compiled.memory_analysis()
        rec[mode] = {
            "flops_per_dev": cost["flops"],
            "analytic_flops_per_dev": ana,
            "mem_bytes_per_dev": cost["mem_bytes"],
            "collective_bytes_per_dev":
                cost["collectives"]["_total"]["bytes"],
            "collective_count": cost["collectives"]["_total"]["count"],
            "temp_bytes": int(getattr(mem, "temp_size_in_bytes", 0)),
            "compute_s": cost["flops"] / 197e12,
            "memory_s": cost["mem_bytes"] / 819e9,
            "collective_s":
                cost["collectives"]["_total"]["bytes"] / 50e9,
        }
        print(f"[dryrun-lp] {wl.name} {mesh_name} {mode}: "
              f"pivots~{mean_pivots:.0f} "
              f"flops/dev={rec[mode]['flops_per_dev']:.2e} "
              f"collB/dev={rec[mode]['collective_bytes_per_dev']:.2e} "
              f"coll#={rec[mode]['collective_count']:.0f}")
        with open(os.path.join(args.out,
                               f"{wl.name}__{mesh_name}.json"), "w") as f:
            json.dump(rec, f, indent=1)


if __name__ == "__main__":
    main()
