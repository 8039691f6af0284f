"""Pallas TPU kernels for the FAGP hot spots (validated in interpret mode).

The feature kernels are generic over a KernelExpansion's tile builder
(``tile_fn``) — see core/expansions.py for the registry.

hermite_phi — fused feature construction (generic kernel + the Hermite tile
              for paper Eq. 19)
rff_phi     — random-Fourier-feature tile builder (RFF-SE / RFF-Matern)
gram        — the fit's Gram moments  B = I + D Phi^T Phi D / sig2 and
              b = Phi^T y, row chunk by row chunk (ops.fused_fit_moments:
              each chunk's Phi from hermite_phi, then this kernel)
phi_gram    — a GP bank's moments, feature tiles built inside the Gram
              accumulation (no Phi in HBM for any tenant)
diag_quad   — predictive-variance diagonal without the N* x N* covariance
knn         — blocked streaming top-k neighbor search (the Vecchia
              conditioning-set builder; no N x N distance matrix)
"""
from . import diag_quad, gram, hermite_phi, knn, ops, phi_gram, ref, rff_phi
from .ops import expansion_phi as expansion_phi_op        # noqa: F401
from .ops import hermite_phi as hermite_phi_op            # noqa: F401
from .ops import diag_quad as diag_quad_op                # noqa: F401
from .ops import scaled_gram as scaled_gram_op            # noqa: F401
from .ops import fused_fit_moments as fused_fit_moments_op  # noqa: F401
