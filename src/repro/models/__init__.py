"""repro.models — composable model zoo; every matmul goes through repro.core.blas."""

from repro.models.layers import SCOPES
from repro.models.model import Model, build_model, cross_entropy

__all__ = ["SCOPES", "Model", "build_model", "cross_entropy"]
