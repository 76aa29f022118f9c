"""Grouped matmul over the experts an expert layer holds."""

from repro.kernels.grouped_matmul.ops import grouped_matmul

__all__ = ["grouped_matmul"]
