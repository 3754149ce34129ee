"""retrace-hazard: patterns that defeat the compile-once contract.

The repo's serving-grade invariant (`tracing.TRACE_COUNTS`, ROADMAP) is
that repeated fits with identical static configuration reuse one compiled
program.  Three statically-detectable ways to break it:

1. **jit construction in a host loop** — ``jax.jit(...)`` /
   ``functools.partial(jax.jit, ...)`` / ``shard_map(...)`` called inside
   a ``for``/``while`` body builds a fresh wrapper (fresh cache) per
   iteration: every call re-traces.  Build the wrapper once outside (or
   behind `functools.lru_cache`, as the sharded program builders do).
2. **structure rebuild in a device loop** — a ``SampleTreeJax(...)``
   construction or any ``*.init(...)`` call inside a `lax` loop body
   re-materialises the O(n) heap per opened center; the incremental
   `TiledSampleTree.refresh` epilogue path exists precisely to avoid
   this (generalizes the PR-2 source-grep acceptance guard).
3. **data-dependent statics** — passing ``int(...)``/``float(...)``/
   ``.item()`` of runtime data as a `static_argnames` keyword compiles
   one program per distinct value.  Shape metadata (``x.shape[0]``,
   ``len(x)``) is exempt: shapes are already part of the cache key.
"""

from __future__ import annotations

import ast

from repro.analysis.findings import Finding
from repro.analysis.registry import rule
from repro.analysis.rules._common import dotted_name, walk_own

_JIT_BUILDERS = {"jax.jit", "jit"}
_SHARD_MAP = {"shard_map", "jax.shard_map",
              "jax.experimental.shard_map.shard_map"}
_REBUILD_CTORS = {"SampleTreeJax"}
_SCALARIZERS = {"int", "float"}
_SHAPE_ATTRS = {"shape", "ndim", "size"}


def _is_jit_construction(call: ast.Call) -> bool:
    name = dotted_name(call.func)
    if name in _JIT_BUILDERS or name in _SHARD_MAP:
        return True
    if name in ("functools.partial", "partial") and call.args:
        return dotted_name(call.args[0]) in _JIT_BUILDERS
    return False


def _check_host_loops(ctx):
    """Sub-check 1: wrapper construction inside for/while bodies."""
    for node in ast.walk(ctx.tree):
        if not isinstance(node, (ast.For, ast.While)):
            continue
        for child in walk_own(node):
            if isinstance(child, ast.Call) and _is_jit_construction(child):
                name = dotted_name(child.func) or "jit"
                yield Finding(
                    path=ctx.path, line=child.lineno, rule="retrace-hazard",
                    message=(f"'{name}(...)' constructed inside a loop body "
                             "builds a fresh program cache per iteration — "
                             "hoist it (or lru_cache the builder)"),
                )


def _check_lax_rebuilds(ctx):
    """Sub-check 2: O(n) structure rebuilds inside lax loop bodies."""
    for fn in ctx.lax_body_functions():
        for child in walk_own(fn):
            if not isinstance(child, ast.Call):
                continue
            name = dotted_name(child.func)
            if name in _REBUILD_CTORS:
                yield Finding(
                    path=ctx.path, line=child.lineno, rule="retrace-hazard",
                    message=(f"'{name}(...)' constructed inside lax loop "
                             f"body '{fn.name}' rebuilds the O(n) heap per "
                             "iteration — use the incremental refresh path"),
                )
            elif isinstance(child.func, ast.Attribute) \
                    and child.func.attr == "init":
                recv = dotted_name(child.func.value) or "<expr>"
                yield Finding(
                    path=ctx.path, line=child.lineno, rule="retrace-hazard",
                    message=(f"'{recv}.init(...)' inside lax loop body "
                             f"'{fn.name}' rebuilds the sample structure "
                             "per opened center — refresh incrementally "
                             "outside the loop preamble"),
                )


def _shape_derived(node: ast.expr) -> bool:
    """True when the expression only reads shape metadata."""
    for child in [node, *walk_own(node)]:
        if isinstance(child, ast.Attribute) and child.attr in _SHAPE_ATTRS:
            return True
        if isinstance(child, ast.Call) and dotted_name(child.func) == "len":
            return True
    return False


def _check_data_dependent_statics(ctx, project):
    """Sub-check 3: int()/float()/.item() flowing into static kwargs."""
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        callee = dotted_name(node.func)
        statics = project.jit_statics.get(callee)
        if not statics:
            continue
        for kw in node.keywords:
            if kw.arg not in statics:
                continue
            for inner in [kw.value, *walk_own(kw.value)]:
                if not isinstance(inner, ast.Call):
                    continue
                name = dotted_name(inner.func)
                bad = None
                if name in _SCALARIZERS and inner.args \
                        and not _shape_derived(inner.args[0]):
                    bad = f"{name}(...)"
                elif isinstance(inner.func, ast.Attribute) \
                        and inner.func.attr == "item":
                    bad = ".item()"
                if bad:
                    yield Finding(
                        path=ctx.path, line=inner.lineno,
                        rule="retrace-hazard",
                        message=(f"{bad} feeding static '{kw.arg}' of jit "
                                 f"function '{callee}' compiles one program "
                                 "per runtime value"),
                    )
                    break


@rule("retrace-hazard",
      doc="jit wrappers built in loops, heap rebuilds in lax bodies, and "
          "data-dependent values in static argnums")
def check(ctx, project):
    yield from _check_host_loops(ctx)
    yield from _check_lax_rebuilds(ctx)
    yield from _check_data_dependent_statics(ctx, project)
