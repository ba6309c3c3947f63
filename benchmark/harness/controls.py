"""Controls: the program with one stated guarantee broken, to show that
the comparison which decides ``correct`` fails when it should. Never
armed by ``run.py``; ``control.py`` and the tests arm them.

``loose``     every query answered under the program's own ``loose`` hint:
              the f32-widened device mask accepted with no exact host
              refinement (the step below the configuration's "answers
              exact under the store's f64 semantics").
``drop-row``  refinement loses the last row of every answer that has more
              than one: an answer altered where it is produced.
``swap-attr`` every answer's first Integer attribute comes back one too
              high: a row that carries another row's attribute.
"""

from __future__ import annotations

import numpy as np


def arm(name: str):
    """Break the guarantee; returns the function that mends it."""
    if name == "loose":
        from geomesa_tpu.planning.hints import QueryHints
        from geomesa_tpu.planning.planner import QueryPlanner

        real = QueryPlanner._refine_and_post

        def loose(self, plan, candidates, certain, hints, *a, **kw):
            import dataclasses

            hints = (QueryHints(loose=True) if hints is None
                     else dataclasses.replace(hints, loose=True))
            return real(self, plan, candidates, certain, hints, *a, **kw)

        QueryPlanner._refine_and_post = loose
        return lambda: setattr(QueryPlanner, "_refine_and_post", real)
    if name == "drop-row":
        from geomesa_tpu.planning.planner import QueryPlanner

        real_post = QueryPlanner._refine_and_post

        def short(self, *a, **kw):
            fc = real_post(self, *a, **kw)
            return fc.take(np.arange(len(fc) - 1)) if len(fc) > 1 else fc

        QueryPlanner._refine_and_post = short
        return lambda: setattr(QueryPlanner, "_refine_and_post", real_post)
    if name == "swap-attr":
        from geomesa_tpu.planning.planner import QueryPlanner

        real_swap = QueryPlanner._refine_and_post

        def swapped(self, *a, **kw):
            fc = real_swap(self, *a, **kw)
            name = next(a.name for a in fc.sft.attributes if a.type == "Integer")
            if len(fc):
                fc.columns[name] = np.asarray(fc.columns[name]) + 1
            return fc

        QueryPlanner._refine_and_post = swapped
        return lambda: setattr(QueryPlanner, "_refine_and_post", real_swap)
    raise ValueError(f"unknown control {name!r}")
