"""Pallas TPU kernels.

What every kernel file shares: the interpret-mode rule and the VMEM budget
its block sizes are planned against.
"""

import jax as _jax

# One v5e TensorCore has 128 MiB of VMEM; a kernel gets what its
# ``vmem_limit_bytes`` asks for (the compiler's default is 16 MiB). Kernels
# whose blocks grow with the model (decode attention, the fused decode
# blocks, the int8 matmul) ask for VMEM_LIMIT_BYTES and size their blocks so
# their own estimate stays under VMEM_BLOCK_BUDGET; the quarter between the
# two absorbs what the estimate cannot see (the compiler's internal scratch,
# relayout copies).
VMEM_LIMIT_BYTES = 32 * 2**20
VMEM_BLOCK_BUDGET = 24 * 2**20


def fits_vmem(nbytes):
    """Whether a kernel's own estimate of one grid step stays in the budget."""
    return nbytes <= VMEM_BLOCK_BUDGET


def interpret():
    """Pallas interpret mode: on for the CPU test mesh, off on any real
    backend — a kernel never reaches a chip through the interpreter."""
    return _jax.default_backend() == "cpu"
