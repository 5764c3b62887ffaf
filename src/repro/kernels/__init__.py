"""Pallas kernels for the GRASP hot paths.

Every kernel here compiles with Mosaic when JAX's default backend is a TPU
and runs in the Pallas interpreter on any other backend. ``interpret()`` is
the one place that choice is made; callers never pass it.
"""
import jax


def interpret() -> bool:
    """True off TPU: the Pallas interpreter stands in for Mosaic there."""
    return jax.default_backend() != "tpu"
