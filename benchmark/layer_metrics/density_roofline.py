"""Kernels: the density family's share of its roofline (harness/layers.py
``roofline_share``; bytes and operations of the question, not of the
one-hot matmul that answers it: kernels/density.py)."""
from harness.layers import roofline_share


def read(view):
    return roofline_share(view, "density")
