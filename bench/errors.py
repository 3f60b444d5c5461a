"""The error of a run that cannot give a result."""


class BenchError(Exception):
    """A run that cannot give a result (no chip, unknown cell, ...)."""
