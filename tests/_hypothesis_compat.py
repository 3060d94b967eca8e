"""One import point for the property-test library (see requirements-dev.txt)."""
from hypothesis import given, settings, strategies as st  # noqa: F401
