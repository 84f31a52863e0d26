"""The property tests' one import point for ``hypothesis``."""
from hypothesis import given, settings, strategies as st  # noqa: F401
