"""Per-file and cross-file context the rules consume.

`FileContext` wraps one parsed module: source lines for pragma lookup and
the JAX-aware trace classification every rule needs — which functions are
jit-entry points (and with which `static_argnames`), which are
`lax.fori_loop`/`while_loop`/`scan` bodies, which are `shard_map`
programs, and which are `functools.lru_cache` builders.

`Project` is the two-pass half: a symbol table built over *all* analyzed
files before any rule runs, so e.g. the hashability rule can resolve an
annotation like ``schedule: BatchSchedule | None`` to the frozen-ness of
the `BatchSchedule` dataclass defined in another module.

Suppression: a ``# repro: disable=<rule>[,<rule>...]`` pragma on the
flagged line or the line directly above it silences that rule there
(documented in docs/analysis.md).
"""

from __future__ import annotations

import ast
import dataclasses
import re
from typing import Optional

__all__ = ["FileContext", "Project", "DataclassInfo", "TracedFunction",
           "dotted_name"]

_PRAGMA = re.compile(r"#\s*repro:\s*disable=([\w,\- ]+)")

# Call targets that wrap a function into a jit program.
_JIT_NAMES = {"jax.jit", "jit"}
_PARTIAL_NAMES = {"functools.partial", "partial"}
_SHARD_MAP_NAMES = {"shard_map", "jax.shard_map",
                    "jax.experimental.shard_map.shard_map"}
_LRU_NAMES = {"functools.lru_cache", "lru_cache", "functools.cache", "cache"}
# (call target, positional index of the traced body function[s])
_LAX_BODY_ARGS = {
    "jax.lax.fori_loop": (2,),
    "lax.fori_loop": (2,),
    "jax.lax.while_loop": (0, 1),
    "lax.while_loop": (0, 1),
    "jax.lax.scan": (0,),
    "lax.scan": (0,),
    "jax.lax.cond": (1, 2),
    "lax.cond": (1, 2),
}


def dotted_name(node: ast.AST) -> Optional[str]:
    """``a.b.c`` for Name/Attribute chains, else None."""
    if isinstance(node, ast.Name):
        return node.id
    if isinstance(node, ast.Attribute):
        base = dotted_name(node.value)
        return None if base is None else f"{base}.{node.attr}"
    return None


def _static_argnames(call: ast.Call) -> tuple:
    for kw in call.keywords:
        if kw.arg == "static_argnames":
            v = kw.value
            if isinstance(v, ast.Constant) and isinstance(v.value, str):
                return (v.value,)
            if isinstance(v, (ast.Tuple, ast.List)):
                return tuple(e.value for e in v.elts
                             if isinstance(e, ast.Constant)
                             and isinstance(e.value, str))
    return ()


def _jit_call_statics(call: ast.Call) -> Optional[tuple]:
    """static_argnames if `call` is jax.jit(...), else None."""
    if dotted_name(call.func) in _JIT_NAMES:
        return _static_argnames(call)
    return None


@dataclasses.dataclass(frozen=True)
class DataclassInfo:
    """Hashability-relevant facts about one project class definition."""

    name: str
    is_dataclass: bool
    frozen: bool
    eq: bool
    unsafe_hash: bool
    defines_hash: bool

    @property
    def unhashable(self) -> bool:
        # dataclass(eq=True) (the default) sets __hash__ = None unless
        # frozen/unsafe_hash/an explicit __hash__ restores it.
        return (self.is_dataclass and self.eq and not self.frozen
                and not self.unsafe_hash and not self.defines_hash)


@dataclasses.dataclass
class TracedFunction:
    """One function that executes under trace (or builds cache keys)."""

    node: ast.FunctionDef
    kind: str            # "jit" | "lax-body" | "shard-map" | "nested"
    statics: frozenset   # static param names ("jit" only; else empty)


def _classify_class(node: ast.ClassDef) -> DataclassInfo:
    is_dc, frozen, eq, unsafe = False, False, True, False
    for dec in node.decorator_list:
        target = dec.func if isinstance(dec, ast.Call) else dec
        if dotted_name(target) in ("dataclasses.dataclass", "dataclass"):
            is_dc = True
            if isinstance(dec, ast.Call):
                for kw in dec.keywords:
                    if isinstance(kw.value, ast.Constant):
                        if kw.arg == "frozen":
                            frozen = bool(kw.value.value)
                        elif kw.arg == "eq":
                            eq = bool(kw.value.value)
                        elif kw.arg == "unsafe_hash":
                            unsafe = bool(kw.value.value)
    defines_hash = any(isinstance(b, ast.FunctionDef) and b.name == "__hash__"
                       for b in node.body)
    return DataclassInfo(name=node.name, is_dataclass=is_dc, frozen=frozen,
                         eq=eq, unsafe_hash=unsafe,
                         defines_hash=defines_hash)


class FileContext:
    """One parsed module plus its JAX trace classification."""

    def __init__(self, path: str, source: str):
        self.path = path                      # repo-relative, "/"-separated
        self.source = source
        self.lines = source.splitlines()
        self.tree = ast.parse(source, filename=path)
        self.functions: list[ast.FunctionDef] = [
            n for n in ast.walk(self.tree)
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
        ]
        self.classes: dict[str, DataclassInfo] = {
            n.name: _classify_class(n)
            for n in ast.walk(self.tree) if isinstance(n, ast.ClassDef)
        }
        self._classify_traced()

    # -- pragma suppression -------------------------------------------------

    def suppressed(self, rule: str, line: int) -> bool:
        for ln in (line, line - 1):
            if 1 <= ln <= len(self.lines):
                m = _PRAGMA.search(self.lines[ln - 1])
                if m and rule in [s.strip() for s in m.group(1).split(",")]:
                    return True
        return False

    # -- trace classification -----------------------------------------------

    def _classify_traced(self) -> None:
        by_name: dict[str, list[ast.FunctionDef]] = {}
        for fn in self.functions:
            by_name.setdefault(fn.name, []).append(fn)

        self.traced: dict[ast.FunctionDef, TracedFunction] = {}
        self.lru_cached: list[ast.FunctionDef] = []
        # jit-wrapped *names* (defs or module-level assignments) -> statics;
        # the retrace-hazard rule resolves call sites against this.
        self.jit_statics: dict[str, frozenset] = {}

        def mark(fn, kind, statics=frozenset()):
            cur = self.traced.get(fn)
            if cur is None or cur.kind == "nested":
                self.traced[fn] = TracedFunction(fn, kind,
                                                 frozenset(statics))

        # 1. decorators
        for fn in self.functions:
            for dec in fn.decorator_list:
                target = dec.func if isinstance(dec, ast.Call) else dec
                name = dotted_name(target)
                if name in _JIT_NAMES:
                    statics = (_static_argnames(dec)
                               if isinstance(dec, ast.Call) else ())
                    mark(fn, "jit", statics)
                    self.jit_statics[fn.name] = frozenset(statics)
                elif (isinstance(dec, ast.Call) and name in _PARTIAL_NAMES
                      and dec.args
                      and dotted_name(dec.args[0]) in _JIT_NAMES):
                    statics = _static_argnames(dec)
                    mark(fn, "jit", statics)
                    self.jit_statics[fn.name] = frozenset(statics)
                elif name in _LRU_NAMES:
                    self.lru_cached.append(fn)

        # 2. call forms: jax.jit(f, ...), shard_map(f, ...), lax bodies
        shard_mapped: dict[str, str] = {}     # alias -> program fn name
        for node in ast.walk(self.tree):
            if not isinstance(node, ast.Call):
                continue
            name = dotted_name(node.func)
            if name in _JIT_NAMES and node.args:
                arg = node.args[0]
                statics = _static_argnames(node)
                target = dotted_name(arg)
                if target is not None:
                    for fn in by_name.get(target, ()):
                        mark(fn, "jit", statics)
                        self.jit_statics[fn.name] = frozenset(statics)
                    # jax.jit(shard_map_alias) -> the program is traced
                    prog = shard_mapped.get(target)
                    if prog is not None:
                        for fn in by_name.get(prog, ()):
                            mark(fn, "shard-map")
            elif name in _SHARD_MAP_NAMES and node.args:
                target = dotted_name(node.args[0])
                if target is not None:
                    for fn in by_name.get(target, ()):
                        mark(fn, "shard-map")
            elif name in _LAX_BODY_ARGS:
                for i in _LAX_BODY_ARGS[name]:
                    if i < len(node.args):
                        target = dotted_name(node.args[i])
                        if target is not None:
                            for fn in by_name.get(target, ()):
                                mark(fn, "lax-body")
        for node in ast.walk(self.tree):
            if isinstance(node, ast.Assign) and isinstance(node.value,
                                                           ast.Call):
                call_name = dotted_name(node.value.func)
                for tgt in node.targets:
                    if not isinstance(tgt, ast.Name):
                        continue
                    if call_name in _SHARD_MAP_NAMES and node.value.args:
                        prog = dotted_name(node.value.args[0])
                        if prog is not None:
                            shard_mapped[tgt.id] = prog
                            for fn in by_name.get(prog, ()):
                                mark(fn, "shard-map")
                    if _jit_call_statics(node.value) is not None \
                            and node.value.args:
                        self.jit_statics[tgt.id] = frozenset(
                            _static_argnames(node.value))

        # 3. nesting closure: functions defined inside a traced function
        # execute under the same trace.
        changed = True
        while changed:
            changed = False
            for fn in self.functions:
                if fn in self.traced:
                    for inner in ast.walk(fn):
                        if (isinstance(inner, ast.FunctionDef)
                                and inner is not fn
                                and inner not in self.traced):
                            mark(inner, "nested")
                            changed = True

    def lax_body_functions(self) -> list[ast.FunctionDef]:
        out = []
        for fn, info in self.traced.items():
            if info.kind == "lax-body":
                out.append(fn)
        # plus everything nested inside a lax body
        roots = list(out)
        for root in roots:
            for inner in ast.walk(root):
                if isinstance(inner, ast.FunctionDef) and inner is not root \
                        and inner not in out:
                    out.append(inner)
        return out


class Project:
    """Cross-file symbol table, built before any rule runs."""

    def __init__(self, files: list[FileContext]):
        self.files = files
        self.dataclasses: dict[str, DataclassInfo] = {}
        self.jit_statics: dict[str, frozenset] = {}
        for ctx in files:
            self.dataclasses.update(ctx.classes)
            self.jit_statics.update(ctx.jit_statics)
