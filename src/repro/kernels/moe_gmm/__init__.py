"""Grouped matmul over expert-sorted rows (the MoE expert layer)."""
from repro.kernels.moe_gmm.ops import gmm  # noqa: F401
from repro.kernels.moe_gmm.ref import gmm_ref  # noqa: F401
