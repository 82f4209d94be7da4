"""The paper's primary contribution: batched LP solving as a device-native,
shardable primitive (Gurung & Ray 2018, adapted CUDA->TPU/JAX).

Public API:
    LPBatch, LPResult, status codes      — problem/result containers
    GeneralLPBatch, canonicalize         — general-form LPs (senses/ranges/
                                           bounds/min-max, core/forms.py):
                                           every solve_* accepts one
                                           directly; io/mps.py parses MPS
                                           files into them
    solve_batched_jax                    — lockstep pure-JAX batched simplex
                                           (phase-compacted two-loop solve)
    solve_batched_revised                — revised simplex: basis-factor
                                           updates + partial pricing
                                           (``backend="revised"`` on every
                                           solve_* is the same engine)
    solve_batched_compacted              — active-set compaction scheduler
    solve_batched                        — HBM-aware chunked driver (Alg. 1)
    SparseLPBatch, solve_batched_pdhg_sparse
                                         — shared-pattern sparse batches:
                                           one COO pattern across the batch,
                                           (B, nnz) values; PDHG matvecs
                                           scale with nnz, not m*n
    solve_hyperbox                       — box-LP closed form (Sec. 5.6)
    solve_shard_map                      — the batch spread over a mesh,
                                           each chip its own while loop
                                           (``mesh=`` on solve_batched and
                                           the engines; core/transfer.py
                                           moves every one-shot solve's
                                           inputs and answers)
    expert_capacity_lp                   — MoE integration (LP router)
    PRICING_RULES / ALL_PRICING          — pluggable pivot pricing
                                           (``pricing=`` on every solve_*):
                                           dantzig | steepest_edge | devex
                                           | partial
    WarmStart                            — cross-solve state carrier
                                           (``res.warm_start()`` ->
                                           ``solve_*(..., warm=ws)``): basis
                                           + flips + pricing weights for the
                                           simplexes, iterates + primal
                                           weight for PDHG
    branch_and_bound                     — batched MIP branch-and-bound:
                                           frontiers of bound-edited nodes
                                           solved as one warm-started batch
                                           per dispatch (core/branch_bound.py)
"""
from .lp import (  # noqa: F401
    BACKEND_REGISTRY, BACKENDS, BIG, INFEASIBLE, ITERATION_LIMIT, OPTIMAL,
    UNBOUNDED, LPBatch, LPResult, STATUS_NAMES, WarmStart, backend_spec,
    build_tableau, canonicalize_backend, default_max_iters, resolve_backend,
)
from .forms import (  # noqa: F401
    GeneralLPBatch, Recovery, canonical_shape, canonicalize, general_kkt,
    general_violation, prepare_warm, random_general_lp_batch, rebind_bounds,
)
from .pricing import ALL_PRICING, PRICING_RULES, canonicalize_rule  # noqa: F401
from .simplex import (  # noqa: F401
    solve_batched_jax, flops_per_pivot, tableau_elements,
)
from .batching import solve_batched, max_chunk_size  # noqa: F401
from .compaction import (  # noqa: F401
    CompactionConfig, SegmentStat, auto_compact_threshold, auto_segment_k,
    solve_batched_compacted,
)
from .revised import (  # noqa: F401
    auto_refactor_period, revised_elements, solve_batched_revised,
    solve_batched_revised_compacted,
)
from .pdhg import (  # noqa: F401
    default_pdhg_max_iters, pdhg_elements, solve_batched_pdhg,
    solve_batched_pdhg_compacted,
)
from .sparse import (  # noqa: F401
    SparseLPBatch, solve_batched_pdhg_sparse, sparse_matvecs,
    sparse_pdhg_elements,
)
from .hyperbox import solve_hyperbox, solve_hyperbox_ref, hyperbox_as_general_lp  # noqa: F401
from .reference import (  # noqa: F401
    random_lp_batch, random_sparse_lp_batch, solve_batched_reference,
    solve_batched_reference_detailed, solve_dual_reference,
)
from .distributed import solve_shard_map  # noqa: F401
from .lp_router import expert_capacity_lp  # noqa: F401
from .branch_bound import (  # noqa: F401
    BnBResult, branch_and_bound, safe_dual_bound,
)
