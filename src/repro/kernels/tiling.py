"""Batch-tile sizing shared by the Pallas tile kernels.

Every tile kernel keeps ``tile_b`` LPs' state blocks resident in VMEM and
lets the Pallas pipeline double-buffer each input and output block across
grid steps.  Two limits bound ``tile_b``:

* **VMEM.** Each kernel asks Mosaic for ``VMEM_LIMIT_BYTES`` of scoped VMEM
  (`compiler_params`) and sizes its tile against that grant with a per-LP
  working-set model (pipelined blocks plus live full-tile temporaries).
  The default scoped limit, 16 MiB on a v5e, refused 28x28 tableau tiles
  of 128 LPs; the grant below is half of one v5e TensorCore's 128 MiB.
* **Compile time.** Mosaic unrolls every tile-wide op over (8, 128) vector
  registers, so a kernel's compile time grows linearly with the tile:
  about 8 s for a 1 MiB 28x28 tableau tile and 20 s for a 2 MiB one on
  the TPU compiler.  ``TILE_BLOCK_BYTES`` caps the largest per-LP block at
  about 1 MiB per tile.

The tile is always a multiple of the 8-row sublane tiling: Mosaic lays a
(tile_b, k) lane row out in (8, 128) tiles, so a tile of 1-7 LPs would be
a partial sublane block.
"""
from __future__ import annotations

from jax.experimental.pallas import tpu as pltpu

VMEM_LIMIT_BYTES = 64 * 2 ** 20
TILE_BLOCK_BYTES = 2 ** 20
SUBLANES = 8
MAX_TILE_B = 512


def round_up(v: int, k: int) -> int:
    return -(-v // k) * k


def pick_tile(per_lp_vmem: int, per_lp_block: int,
              vmem_budget: int = VMEM_LIMIT_BYTES) -> int:
    """Largest sublane-multiple tile whose working set fits ``vmem_budget``
    and whose largest block stays under ``TILE_BLOCK_BYTES``; never below
    one sublane block of 8 LPs (a working set too large even for that is
    left to the compiler, which refuses it on the chip)."""
    tile = min(int(vmem_budget) // int(per_lp_vmem),
               TILE_BLOCK_BYTES // int(per_lp_block), MAX_TILE_B)
    return max(SUBLANES, tile // SUBLANES * SUBLANES)


def compiler_params() -> pltpu.CompilerParams:
    """Mosaic parameters of every tile kernel: the scoped VMEM grant the
    tile pickers budget against (ignored by the interpreter)."""
    return pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT_BYTES)
