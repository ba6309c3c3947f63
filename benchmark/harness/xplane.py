"""Reduction of a profiler trace (``.xplane.pb``, read with
``jax.profiler.ProfileData``) to what the benchmark reports: the seconds
in which an operation ran on the device (the union of the device-op
intervals, averaged over the chips), the traced window, device time per
operation name, and the longest idle gaps, each laid to what the host was
doing in it by the benchmark's own ``bench:`` annotations.
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OP_LINES = ("XLA Ops",)
WINDOW = "bench:window"


def short_name(name: str) -> str:
    """A device op as the trace names it is a whole line of HLO text; kept
    are its left-hand side and the shape of its (first) result:
    ``_pallas_block_scan.1 s32[32,4,128]``."""
    lhs, sep, rhs = name.partition(" = ")
    if not sep:
        return name[:80]
    shape = re.match(r"\(?([a-z0-9]+\[[0-9,]*\])", rhs)
    return (lhs.lstrip("%") + (" " + shape.group(1) if shape else ""))[:80]


def load(path: str) -> dict:
    """{"device": {plane: [(name, start_ns, dur_ns)]}, "host": [same]}
    from one .xplane.pb. Device events are those of the op lines of each
    TPU plane (every line when a plane names none so); host events are
    the ``bench:`` annotations of any host line."""
    from jax.profiler import ProfileData

    device, host = {}, []
    for plane in ProfileData.from_file(path).planes:
        lines = list(plane.lines)
        if DEVICE_PLANE.match(plane.name):
            ops = [ln for ln in lines if ln.name in OP_LINES] or lines
            device[plane.name] = [
                (short_name(ev.name), float(ev.start_ns), float(ev.duration_ns))
                for ln in ops for ev in ln.events
            ]
        elif plane.name.startswith("/host:"):
            host.extend(
                (ev.name, float(ev.start_ns), float(ev.duration_ns))
                for ln in lines for ev in ln.events if ev.name.startswith("bench:")
            )
    return {"device": device, "host": host}


def newest_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")),
                   key=os.path.getmtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def union(intervals) -> list:
    """Sorted, merged [start, end] pairs."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def gaps(busy, lo, hi) -> list:
    """The complement of merged ``busy`` inside [lo, hi]."""
    out, at = [], lo
    for s, e in busy:
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if hi > at:
        out.append((at, hi))
    return out


def _covering(host, lo, hi) -> str:
    """What the host was doing in the gap [lo, hi]: the shortest (so the
    innermost) annotation that holds the gap's middle; failing that, the
    one that overlaps most of the gap."""
    mid = (lo + hi) / 2
    inner = [(d, name) for name, s, d in host if name != WINDOW and s <= mid <= s + d]
    if inner:
        return min(inner)[1]
    over = [(min(s + d, hi) - max(s, lo), name) for name, s, d in host if name != WINDOW]
    over = [c for c in over if c[0] > 0]
    return max(over)[1] if over else "host: no bench annotation"


def reduce(events: dict, top: int = 10) -> dict:
    """{"window_s", "busy_s", "ops": {name: seconds}, "device_ops": [[name,
    s]...], "idle_gaps": [[what, s]...], "n_device_events", "planes"}.
    The window is the ``bench:window`` annotation when the trace has one,
    else the extent of the device events."""
    device, host = events["device"], events["host"]
    every = [(s, s + d) for evs in device.values() for _, s, d in evs]
    win = [(s, s + d) for name, s, d in host if name == WINDOW]
    if win:
        lo, hi = win[0]
    elif every:
        lo, hi = min(s for s, _ in every), max(e for _, e in every)
    else:
        lo = hi = 0.0
    ops, busy_ns, per_plane = {}, 0.0, {}
    for plane, evs in device.items():
        merged = union(_clip([(s, s + d) for _, s, d in evs], lo, hi))
        per_plane[plane] = merged
        busy_ns += sum(e - s for s, e in merged)
        for name, s, d in evs:
            if s + d > lo and s < hi:
                ops[name] = ops.get(name, 0.0) + d / 1e9 / max(len(device), 1)
    n_planes = max(len(device), 1)
    idle = []
    if per_plane:
        first = sorted(per_plane)[0]  # gaps are named on one chip: the first
        for s, e in sorted(gaps(per_plane[first], lo, hi), key=lambda g: g[0] - g[1])[:top]:
            idle.append([_covering(host, s, e), (e - s) / 1e9])
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy_ns / 1e9 / n_planes,
        "ops": ops,
        "device_ops": [[k, v] for k, v in sorted(ops.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": idle,
        "n_device_events": sum(len(v) for v in device.values()),
        "planes": sorted(device),
    }


def family_seconds(ops: dict, pattern: str) -> float:
    rx = re.compile(pattern)
    return sum(v for k, v in ops.items() if rx.search(k))
