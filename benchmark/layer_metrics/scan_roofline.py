"""Kernels: the scan family's share of its roofline (harness/layers.py
``roofline_share``; bytes and operations from kernels/scan.py)."""
from harness.layers import roofline_share


def read(view):
    return roofline_share(view, "scan")
