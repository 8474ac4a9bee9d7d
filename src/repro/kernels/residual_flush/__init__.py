from repro.kernels.residual_flush.ops import paged_residual_flush  # noqa: F401
