"""Shared by the readers of what PR 24 put inside the program's spans:
the window's spans once each, a span's segments (``attrs["segments"]``:
{name: wall_s}), and the sums under one ``query_many`` root.
A program that has no such span, segment or attribute (the parent of
PR 24) gives every reader here nothing to read: None."""

from harness.stats import median


def spans(view, name=None, roots=None):
    """The window's spans, each once (the harness lists a root twice: as
    its trace's root and among the trace's finished spans), of one
    ``name`` and of roots named in ``roots`` where those are given."""
    seen, out = set(), []
    for s in view["spans"]:
        if s["id"] in seen:
            continue
        seen.add(s["id"])
        if (name is None or s["name"] == name) and (roots is None or s["root"] in roots):
            out.append(s)
    return out


def segment_ms(view, span_name, segments):
    """Median, in milliseconds, over the ``span_name`` spans that carry
    any of ``segments``, of the wall time inside those segments. A span
    without them is no sample: of a fused group one member pulls."""
    got = []
    for s in spans(view, span_name):
        segs = s["attrs"].get("segments") or {}
        have = [segs[k] for k in segments if k in segs]
        if have:
            got.append(sum(have) * 1e3)
    return median(got) if got else None


def many_ms(view, name):
    """Per ``query_many`` root the summed wall of its direct children
    called ``name`` (a ``dispatch`` nested in the staging's own is not
    counted twice); the median over the window's roots, milliseconds."""
    sums = {s["id"]: 0.0 for s in spans(view, "query_many") if s["parent"] is None}
    for s in spans(view, name, roots=("query_many",)):
        if s["parent"] in sums:
            sums[s["parent"]] += s["dur_s"] * 1e3
    return median(list(sums.values())) if sums else None
