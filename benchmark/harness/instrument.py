"""Observation from outside: wrappers the benchmark puts round the
program's layer entry points (as chip_smoke.py's ``KernelCalls`` does),
JAX's own compile accounting, and the program's spans read out raw.

Nothing here changes what the program computes. ``Recorder`` is armed in
a ``--trace 1`` run only; ``CompileEvents`` listens in every run (a
listener costs nothing until something compiles).
"""

from __future__ import annotations

import glob
import importlib
import json
import os

class Recorder:
    """Armed in a traced run. Wraps every entry point the files under
    ``annotations/`` list ([label, module, class or null, attribute]) with
    a ``jax.profiler.TraceAnnotation`` named ``bench:<label>``: a host
    span on the device events' clock, so that an idle gap can be laid to
    what the host did. Then lets every kernel family
    (``kernels/<family>.json`` and its module) install its own call
    records: ``calls`` {family: [record, ...]}."""

    def __init__(self, here: str):
        import jax.profiler

        self.annotation = jax.profiler.TraceAnnotation
        self.calls: dict = {}
        self.missing: list = []
        self._undo = []
        for path in sorted(glob.glob(os.path.join(here, "annotations", "*.json"))):
            with open(path) as fh:
                for label, mod, cls, attr in json.load(fh):
                    owner = importlib.import_module(mod)
                    if cls is not None:
                        owner = getattr(owner, cls)
                    if getattr(owner, attr, None) is None:
                        self.missing.append(label)  # renamed since: no span, said in the run
                        continue
                    self.patch(owner, attr, self._annotated(f"bench:{label}"))
        for family in kernel_families(here).values():
            importlib.import_module("kernels." + family["module"]).install(self, family)

    def patch(self, owner, attr, make) -> None:
        """Put ``make(current function)`` in the function's place."""
        fn = getattr(owner, attr)
        self._undo.append((owner, attr, fn))
        setattr(owner, attr, make(fn))

    def _annotated(self, name):
        def make(fn):
            def wrapped(*a, **kw):
                with self.annotation(name):
                    return fn(*a, **kw)

            return wrapped

        return make

    def close(self) -> None:
        for owner, attr, fn in reversed(self._undo):
            setattr(owner, attr, fn)
        self._undo = []


def kernel_families(here: str) -> dict:
    """{family: its ``kernels/<family>.json``}."""
    out = {}
    for path in sorted(glob.glob(os.path.join(here, "kernels", "*.json"))):
        with open(path) as fh:
            fam = json.load(fh)
        out[fam["family"]] = fam
    return out


class CompileEvents:
    """JAX's own compile accounting (jax.monitoring): compile requests,
    persistent-cache hits, and seconds inside the backend compiler."""

    def __init__(self):
        import jax.monitoring as mon

        self.requests = self.hits = 0
        self.seconds = 0.0
        mon.register_event_duration_secs_listener(self._dur)
        mon.register_event_listener(self._evt)

    def _dur(self, name, secs, **kw):
        if name == "/jax/core/compile/backend_compile_duration":
            self.requests += 1
            self.seconds += secs

    def _evt(self, name, **kw):
        if name == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def snapshot(self):
        return self.requests, self.hits, self.seconds


def arm_spans(buffer: int) -> None:
    """Every root is kept (``geomesa.obs.trace.sample=1``) in a buffer
    large enough for the window."""
    from geomesa_tpu import conf
    from geomesa_tpu.obs import trace

    conf.OBS_TRACE_SAMPLE.set(1)
    conf.OBS_TRACE_BUFFER.set(int(buffer))
    trace.install(trace.Tracer())


def raw_spans(t_lo: float, t_hi: float) -> list:
    """The program's spans as plain dicts, roots that began inside
    [t_lo, t_hi) on ``time.perf_counter``: {trace, root, id, parent, name,
    t0, dur_s, self_s, attrs}. A span's self time is its duration minus
    the part its children cover (children of one parent do not overlap
    here except flush workers, whose sum is capped at the parent)."""
    from geomesa_tpu.obs import trace

    out = []
    for tr in trace.tracer().traces():
        if not (t_lo <= tr.root.t0 < t_hi):
            continue
        spans = [tr.root] + list(tr.spans)
        child = {}
        for s in spans:
            if s.parent_id is not None:
                child[s.parent_id] = child.get(s.parent_id, 0.0) + s.dur_s
        for s in spans:
            out.append({
                "trace": tr.trace_id, "root": tr.name, "id": s.span_id,
                "parent": s.parent_id, "name": s.name, "t0": s.t0, "dur_s": s.dur_s,
                "self_s": max(s.dur_s - child.get(s.span_id, 0.0), 0.0),
                "attrs": dict(s.attrs or {}),
            })
    return out
