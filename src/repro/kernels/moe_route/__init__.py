"""The expert layer's routing rows: a dispatch gather and a gated combine
that move only the held pairs' rows, and the XLA forms that move every
row of the pair buffer."""
from repro.kernels.moe_route.ops import combine, dispatch, use_kernel  # noqa: F401
from repro.kernels.moe_route.ref import (  # noqa: F401
    combine_ref, dispatch_ref, slots)
