"""Utility layer (reference: easydist/utils/)."""

from .timer import time_per_call  # noqa: F401
from .testing import cpu_mesh  # noqa: F401
