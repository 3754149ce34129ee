"""pallas-tile-shape: kernel tiles must divide, be annotated, and be 2-D.

Three checks, scoped to ``kernels/*.py``:

1. **divisibility** — a function that issues a ``pl.pallas_call`` whose
   grid floor-divides a dimension by a block parameter must carry a
   matching guard: an ``assert ... % ... == 0`` or a ``_pad_to``/
   ``pad_to`` padding call.  A grid of ``n // block_n`` with no guard
   silently drops the ragged tail off-TPU and mis-tiles on it.
2. **autotune annotation** — every hard-coded tile literal (a
   ``block_*: int = 128`` parameter default or a module-level
   ``BLOCK*_ = <int>`` constant) must carry an ``# autotune:`` comment on
   its line recording how the number was chosen (the ROADMAP's
   ``BLOCK_SIZE = 128  # TODO: tune`` anti-pattern: defaults chosen on
   one machine ossify silently; the annotation is the breadcrumb the
   real-hardware autotuning track consumes).
3. **rank-1 blocks** — a ``pl.BlockSpec`` whose block shape is a 1-tuple
   of a tile (a ``block_*``/``tile*`` name or an int literal) tiles a 1-D
   array.  Mosaic refuses such a block unless it spans the whole array:
   XLA lays a 1-D f32 array out in tiles of 1024 and the block's tiling
   must match, and a ``(1,)`` block matches nothing.  Interpret mode
   never notices.  Carry the vector lane-dense as a ``(1, n)`` array in
   ``(1, block)`` blocks instead.
"""

from __future__ import annotations

import ast
import re

from repro.analysis.findings import Finding
from repro.analysis.registry import rule
from repro.analysis.rules._common import dotted_name

_BLOCK_PARAM = re.compile(r"^block(_|$)")
_BLOCK_CONST = re.compile(r"(^|_)BLOCK(_|$)|(^|_)TILE(_|$)")
_PAD_CALLS = {"_pad_to", "pad_to", "_pad_axis", "pad_axis"}
_ANNOTATION = "# autotune:"


def _in_kernels(ctx) -> bool:
    parts = ctx.path.replace("\\", "/").split("/")
    return "kernels" in parts[:-1]


def _annotated(ctx, line: int) -> bool:
    if 1 <= line <= len(ctx.lines):
        return _ANNOTATION in ctx.lines[line - 1]
    return False


def _param_defaults(fn: ast.FunctionDef):
    """(arg, default) pairs for positional and keyword-only params."""
    args = fn.args
    pos = args.posonlyargs + args.args
    for a, d in zip(pos[len(pos) - len(args.defaults):], args.defaults):
        yield a, d
    for a, d in zip(args.kwonlyargs, args.kw_defaults):
        if d is not None:
            yield a, d


def _check_annotations(ctx):
    for fn in ctx.functions:
        for a, d in _param_defaults(fn):
            if _BLOCK_PARAM.match(a.arg) and isinstance(d, ast.Constant) \
                    and isinstance(d.value, int) \
                    and not isinstance(d.value, bool) \
                    and not _annotated(ctx, a.lineno):
                yield Finding(
                    path=ctx.path, line=a.lineno, rule="pallas-tile-shape",
                    severity="warning",
                    message=(f"hard-coded tile default '{a.arg}={d.value}' "
                             f"in '{fn.name}' needs an '# autotune:' "
                             "annotation recording how it was chosen"),
                )
    for node in ctx.tree.body:
        if isinstance(node, ast.Assign) and isinstance(node.value,
                                                       ast.Constant) \
                and isinstance(node.value.value, int) \
                and not isinstance(node.value.value, bool):
            for t in node.targets:
                if isinstance(t, ast.Name) and _BLOCK_CONST.search(t.id) \
                        and not _annotated(ctx, node.lineno):
                    yield Finding(
                        path=ctx.path, line=node.lineno,
                        rule="pallas-tile-shape", severity="warning",
                        message=(f"hard-coded tile constant "
                                 f"'{t.id} = {node.value.value}' needs an "
                                 "'# autotune:' annotation"),
                    )


def _block_divisions(fn: ast.FunctionDef):
    """FloorDiv nodes dividing by a block_* name anywhere in `fn`."""
    for node in ast.walk(fn):
        if isinstance(node, ast.BinOp) and isinstance(node.op, ast.FloorDiv) \
                and isinstance(node.right, ast.Name) \
                and _BLOCK_PARAM.match(node.right.id):
            yield node


def _has_guard(fn: ast.FunctionDef) -> bool:
    for node in ast.walk(fn):
        if isinstance(node, ast.Assert):
            for inner in ast.walk(node.test):
                if isinstance(inner, ast.BinOp) and \
                        isinstance(inner.op, ast.Mod):
                    return True
        if isinstance(node, ast.Call):
            name = dotted_name(node.func)
            if name and name.split(".")[-1] in _PAD_CALLS:
                return True
    return False


def _is_tile(node: ast.AST) -> bool:
    if isinstance(node, ast.Constant):
        return isinstance(node.value, int) and not isinstance(node.value,
                                                              bool)
    return isinstance(node, ast.Name) and bool(
        _BLOCK_PARAM.match(node.id) or node.id.lower().startswith("tile"))


def _check_rank1_blocks(ctx):
    for node in ast.walk(ctx.tree):
        if not (isinstance(node, ast.Call) and (dotted_name(node.func) or "")
                .split(".")[-1] == "BlockSpec"):
            continue
        shape = node.args[0] if node.args else next(
            (kw.value for kw in node.keywords if kw.arg == "block_shape"),
            None)
        if isinstance(shape, ast.Tuple) and len(shape.elts) == 1 \
                and _is_tile(shape.elts[0]):
            yield Finding(
                path=ctx.path, line=node.lineno, rule="pallas-tile-shape",
                message=(f"rank-1 BlockSpec '({ast.unparse(shape.elts[0])},)'"
                         " tiles a 1-D array: Mosaic refuses rank-1 blocks "
                         "smaller than the array (XLA tiles 1-D arrays by "
                         "1024) — use a (1, n) array with (1, block) "
                         "blocks"),
            )


def _check_divisibility(ctx):
    for fn in ctx.functions:
        has_pallas = any(
            isinstance(n, ast.Call)
            and (dotted_name(n.func) or "").split(".")[-1] == "pallas_call"
            for n in ast.walk(fn)
        )
        if not has_pallas:
            continue
        divs = list(_block_divisions(fn))
        if divs and not _has_guard(fn):
            yield Finding(
                path=ctx.path, line=divs[0].lineno,
                rule="pallas-tile-shape",
                message=(f"'{fn.name}' floor-divides a grid dimension by "
                         f"'{divs[0].right.id}' without a divisibility "
                         "assert or padding call — the ragged tail "
                         "mis-tiles"),
            )


@rule("pallas-tile-shape",
      doc="BlockSpec/grid constants must divide padded shapes; tile "
          "literals need an '# autotune:' annotation; no rank-1 tile "
          "blocks")
def check(ctx, project):
    if not _in_kernels(ctx):
        return
    yield from _check_annotations(ctx)
    yield from _check_divisibility(ctx)
    yield from _check_rank1_blocks(ctx)
