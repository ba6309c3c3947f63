"""Kernels, mesh stores: the block scan's share of its roofline under
``shard_map`` (harness/layers.py ``roofline_share``). Per chip: the bytes of
every device's candidate blocks at ONE chip's peak over the number of
devices (kernels/scan_mesh.py), against the family's device time, which
``harness/xplane.py:reduce`` keeps as the sum over the device planes over
their number. Over 100% would be a wrong count, not a fast kernel."""
from harness.layers import roofline_share


def read(view):
    return roofline_share(view, "scan_mesh")
