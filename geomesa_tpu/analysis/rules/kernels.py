"""Kernel-purity rule family: recompile and concretization hazards.

The scan tier's whole design rests on static shapes (PERF.md: one
compiled variant per (M bucket, columns, flags, E, R); every cold
variant is a compile on the query path). Three hazard classes creep in
through review:

- ``float()/int()/bool()`` coercion of a *traced* value inside a jitted
  function — concretizes the tracer (TracerError at best, silent
  per-value recompile at worst). Static arguments (``static_argnames``)
  are exempt: coercing those at trace time is the intended pattern;
- data-dependent output shapes (``jnp.nonzero``, ``unique``, one-arg
  ``where``, ...) inside a jitted function — the exact ops the
  bitmask-plane design exists to avoid (block_kernels module doc);
- ``warmup()`` coverage gaps: the warmup walks the variant ladders so
  production queries never compile; if the fused grouping key gains a
  dimension (an E/R-style bucket ladder) that warmup does not walk,
  first queries stall. Any class shipping both ``warmup`` and
  ``scan_submit_many`` must reference every ``fused_<dim>_bucket``
  ladder (the function or its ``FUSED_<DIM>_BUCKETS`` constant),
  directly or one call level down.
"""

from __future__ import annotations

import ast
import re

from geomesa_tpu.analysis.core import Project, Rule, call_name, names_in

KERNEL_SCOPES = ("geomesa_tpu/scan/", "geomesa_tpu/curve/")
COERCIONS = {"float", "int", "bool"}
DYNAMIC_SHAPE_CALLS = {
    "nonzero", "flatnonzero", "argwhere", "unique", "compress", "extract",
}
_DERIV_DEF_RE = re.compile(r"^fused_([a-z0-9]+)_bucket$")


def _jit_static_names(fn) -> "set[str] | None":
    """None when ``fn`` is not jitted; otherwise the set of static
    parameter names (from ``static_argnames``/``static_argnums``)."""
    for dec in fn.decorator_list:
        target = dec.func if isinstance(dec, ast.Call) else dec
        tail = (
            target.attr if isinstance(target, ast.Attribute)
            else target.id if isinstance(target, ast.Name) else ""
        )
        if tail == "jit":
            return _statics_of(dec, fn)
        if tail == "partial" and isinstance(dec, ast.Call):
            if any("jit" in names_in(a) for a in dec.args):
                return _statics_of(dec, fn)
    return None


def _statics_of(dec, fn) -> set[str]:
    params = [a.arg for a in fn.args.posonlyargs + fn.args.args]
    out: set[str] = set()
    if not isinstance(dec, ast.Call):
        return out
    for kw in dec.keywords:
        # jax accepts both the iterable and the bare-scalar forms:
        # static_argnames=("a", "b") / static_argnames="a",
        # static_argnums=(0, 1) / static_argnums=0
        elts = (
            kw.value.elts
            if isinstance(kw.value, (ast.Tuple, ast.List))
            else [kw.value]
        )
        if kw.arg == "static_argnames":
            for e in elts:
                if isinstance(e, ast.Constant) and isinstance(e.value, str):
                    out.add(e.value)
        if kw.arg == "static_argnums":
            for e in elts:
                if isinstance(e, ast.Constant) and isinstance(e.value, int):
                    if 0 <= e.value < len(params):
                        out.add(params[e.value])
    return out


def _jit_functions(sf):
    if sf.tree is None:
        return
    for node in ast.walk(sf.tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            statics = _jit_static_names(node)
            if statics is not None:
                yield node, statics


class KernelTracedCoercionRule(Rule):
    id = "kernel-traced-coercion"
    description = (
        "no float()/int()/bool() coercion of traced values inside jitted "
        "scan/curve kernels (static_argnames are exempt)"
    )
    fix_hint = (
        "keep the value in jnp (astype / jnp.where), or hoist the "
        "coercion to the host caller; if the parameter is genuinely "
        "static, add it to static_argnames"
    )

    def check(self, project: Project):
        for sf in project.python_files():
            if not sf.relpath.startswith(KERNEL_SCOPES):
                continue
            for fn, statics in _jit_functions(sf):
                kwonly = [a.arg for a in fn.args.kwonlyargs]
                params = [a.arg for a in fn.args.posonlyargs + fn.args.args]
                traced = (set(params) | set(kwonly)) - statics - {"self"}
                for node in ast.walk(fn):
                    if not (
                        isinstance(node, ast.Call)
                        and isinstance(node.func, ast.Name)
                        and node.func.id in COERCIONS
                        and node.args
                    ):
                        continue
                    touched = names_in(node.args[0]) & traced
                    if touched:
                        yield self.finding(
                            sf, node.lineno,
                            f"{node.func.id}() coerces traced value(s) "
                            f"{sorted(touched)} inside jitted "
                            f"{fn.name}() — concretization/recompile "
                            "hazard",
                            # line-free key (the baseline contract):
                            # repeated same-shape coercions in one fn
                            # share a key, which suppresses together
                            symbol=(
                                f"{fn.name}:{node.func.id}:"
                                f"{','.join(sorted(touched))}"
                            ),
                        )


class KernelDynamicShapeRule(Rule):
    id = "kernel-dynamic-shape"
    description = (
        "no data-dependent output shapes (nonzero/unique/one-arg where/"
        "compress) inside jitted scan/curve kernels"
    )
    fix_hint = (
        "keep shapes static: emit packed bitmask planes (the "
        "block_kernels pattern) or masked reductions; decode on host"
    )

    def check(self, project: Project):
        for sf in project.python_files():
            if not sf.relpath.startswith(KERNEL_SCOPES):
                continue
            for fn, _statics in _jit_functions(sf):
                for node in ast.walk(fn):
                    if not isinstance(node, ast.Call):
                        continue
                    name = call_name(node)
                    hazard = name in DYNAMIC_SHAPE_CALLS or (
                        name == "where" and len(node.args) == 1
                    )
                    if hazard:
                        yield self.finding(
                            sf, node.lineno,
                            f"{name}() produces a data-dependent shape "
                            f"inside jitted {fn.name}()",
                            symbol=f"{fn.name}:{name}",
                        )


class WarmupCoverageRule(Rule):
    id = "warmup-coverage"
    description = (
        "warmup() must walk every fused_<dim>_bucket variant-key ladder "
        "(reference the derivation fn or its FUSED_<DIM>_BUCKETS "
        "constant) so no fused dispatch compiles at query time"
    )
    fix_hint = (
        "extend warmup's fused ladder loop with the new dimension's "
        "FUSED_<DIM>_BUCKETS entries"
    )

    #: where the ladder dimensions are declared
    KERNEL_MODULE = "geomesa_tpu/scan/block_kernels.py"

    def _dimensions(self, project: Project) -> list[str]:
        sf = project.files.get(self.KERNEL_MODULE)
        if sf is None or sf.tree is None:
            return []
        dims = []
        for node in ast.walk(sf.tree):
            if isinstance(node, ast.FunctionDef):
                m = _DERIV_DEF_RE.match(node.name)
                if m:
                    dims.append(m.group(1))
        return sorted(dims)

    def check(self, project: Project):
        dims = self._dimensions(project)
        if not dims:
            return
        for sf in project.python_files():
            # host-only backends (no kernel dispatch) have nothing to warm
            if sf.tree is None or "block_scan_multi" not in sf.text:
                continue
            for cls in ast.walk(sf.tree):
                if not isinstance(cls, ast.ClassDef):
                    continue
                methods = {
                    n.name: n for n in cls.body
                    if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
                }
                if "warmup" not in methods or "scan_submit_many" not in methods:
                    continue
                warm = methods["warmup"]
                names = names_in(warm)
                # one level of self-method indirection
                for callee in list(names):
                    if callee in methods and callee != "warmup":
                        names |= names_in(methods[callee])
                for dim in dims:
                    fn_name = f"fused_{dim}_bucket"
                    const = f"FUSED_{dim.upper()}_BUCKETS"
                    if fn_name not in names and const not in names:
                        yield self.finding(
                            sf, warm.lineno,
                            f"{cls.name}.warmup() never references "
                            f"{fn_name}()/{const}: the {dim.upper()} "
                            "variant-key ladder would compile at query "
                            "time",
                            symbol=f"{cls.name}.warmup:{dim}",
                        )
