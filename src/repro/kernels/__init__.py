"""Pallas TPU kernels for the paper's compute hot-spots: the batched simplex
pivot loop (simplex_tile.py, phase-compacted two-loop solve + resumable
segment kernels for the active-set compaction scheduler), the batched
restarted-PDHG whole-solve loop (pdhg_tile.py — fused matvec + prox +
restart check in VMEM, ``backend="pdhg"``) and the hyperbox special case
(hyperbox_kernel.py). Compiled by Mosaic on a TPU and run by the Pallas
interpreter elsewhere (``ops.default_interpret``); checked against ref.py /
the pure-JAX engines."""
from .ops import PallasBackend, solve_batched_pallas, solve_hyperbox_pallas  # noqa: F401
from .simplex_tile import (  # noqa: F401
    compacted_dims, full_dims, pick_tile_b, segment_pallas, simplex_pallas,
)
from .pdhg_tile import pdhg_pallas, pick_pdhg_tile_b  # noqa: F401
from .hyperbox_kernel import hyperbox_pallas  # noqa: F401
