"""The paper's three fixed-point testbeds, and HPCG's operator, in JAX
(float64).

- :mod:`repro.problems.jacobi`          — 2-D Laplacian block Jacobi (§3.3.1)
- :mod:`repro.problems.hpcg`            — HPCG's 3-D 27-point operator, block
  Jacobi on z-slabs
- :mod:`repro.problems.value_iteration` — Garnet MDP Bellman / policy eval (§3.3.2)
- :mod:`repro.problems.scf`             — PPP-model Hartree–Fock SCF (§3.3.3)

Numerical fidelity of the paper's experiments (SCF to 1e-14 eV, Jacobi to
1e-6 on a rho=0.9995 map) requires float64, so importing this package
enables JAX x64 mode.  LM model code (:mod:`repro.models`) uses explicit
dtypes throughout and is unaffected.
"""

import jax

jax.config.update("jax_enable_x64", True)

from .hpcg import HPCGProblem  # noqa: E402
from .jacobi import JacobiProblem  # noqa: E402
from .value_iteration import (  # noqa: E402
    GarnetMDP,
    GridWorldMDP,
    PolicyEvaluationProblem,
    ValueIterationProblem,
)
from .scf import PPPChain, SCFProblem, UHFPPP, UHFSCFProblem  # noqa: E402

__all__ = [
    "HPCGProblem",
    "JacobiProblem",
    "GarnetMDP",
    "GridWorldMDP",
    "PolicyEvaluationProblem",
    "ValueIterationProblem",
    "PPPChain",
    "SCFProblem",
    "UHFPPP",
    "UHFSCFProblem",
]
