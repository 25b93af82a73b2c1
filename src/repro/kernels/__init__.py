"""Pallas TPU kernels for the perf-critical compute layers.

flash_attention — LM attention hot spot (GQA/causal/window/softcap)
jacobi_stencil  — paper §3.3.1 five-point sweep
bellman         — paper §3.3.2 Bellman operator
anderson_mix    — paper Eq. 2 fused extrapolation over large states

Each kernel has a pure-jnp oracle in ref.py and a jit'd wrapper in ops.py,
which interprets them on the CPU backend (tests/test_kernels.py) and
compiles them elsewhere; tests/test_chip_compile.py compiles the main
path's kernels for a TPU v5e.
"""

from . import ops as kernel_ops  # noqa: F401
from . import ops as jacobi_ops  # noqa: F401  (JacobiProblem backend alias)
