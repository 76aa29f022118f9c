"""Grouped matmul: rows sorted by group, each group multiplied by its own
matrix, for the experts an expert layer holds (DESIGN.md §19).

The kernel is JAX's megablox ``gmm`` (Pallas, TPU) with its ``custom_vjp``:
the backward's input gradient is ``gmm`` again on the transposed weights,
the weight gradient ``tgmm``.  Its grid walks only the row tiles that
groups fill, so rows past the filled ones cost nothing: they are handed
to one more group that has no matrix here, whose rows the kernel zeroes.
Its op names keep the stems ``gmm`` and ``tgmm`` in a device trace.
Dots run at the TPU's default precision, as the XLA matmuls around them.

Without the kernel (``use_kernel=False``) the same product is
``jax.lax.ragged_dot``, which also gives zeros past the groups.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.megablox import ops as megablox

LANE = 128
MAX_TM = 256      # rows a tile
MAX_TK = 768      # contraction a tile: (256, 768) and (768, 896) blocks
MAX_TN = 896      # keep a double-buffered step inside v5e's scoped VMEM


def _tile(dim: int, cap: int, quantum: int) -> int:
    """The largest multiple of ``quantum`` up to ``cap`` that divides
    ``dim``; ``dim`` itself where none does (small test shapes)."""
    for t in range(min(cap, dim) // quantum * quantum, 0, -quantum):
        if dim % t == 0:
            return t
    return dim


def tiling(m: int, k: int, n: int) -> tuple[int, int, int]:
    """(tm, tk, tn) for an (m, k) x (k, n) product per group."""
    return (_tile(m, MAX_TM, 8), _tile(k, MAX_TK, LANE),
            _tile(n, MAX_TN, LANE))


def grouped_matmul(lhs, rhs, group_sizes, *, use_kernel: bool = True,
                   interpret: bool = False):
    """lhs (m, k), rows sorted by group; rhs (g, k, n); group_sizes (g,)
    int32, summing to at most m.  Row r of group i is ``lhs[r] @ rhs[i]``;
    rows past the groups are zero.  Differentiable in lhs and rhs."""
    if not use_kernel:
        return jax.lax.ragged_dot(lhs, rhs, group_sizes,
                                  preferred_element_type=lhs.dtype)
    m = lhs.shape[0]
    sizes = jnp.concatenate(
        [group_sizes, (m - group_sizes.sum()).astype(jnp.int32)[None]])
    return megablox.gmm(lhs, rhs, sizes, lhs.dtype, tiling, None, None,
                        False, interpret)
