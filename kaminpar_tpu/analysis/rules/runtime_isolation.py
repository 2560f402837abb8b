"""runtime-isolation: pipeline code owns its settings via EngineRuntime.

PR 6 replaced the first-wins ``configure_*`` process globals with per-engine
:class:`~kaminpar_tpu.context.EngineRuntime` ownership — and review found
the one escape no test executed: nested-extension thread-pool workers
resolved the layout-build backend through the *process default* because the
engine's thread-local activation is invisible in pool threads.  The fix was
an explicit per-graph pin (``g._layout_mode = ...``,
``partitioning/deep.py:_nested_partition``).  This rule makes the whole
contract static over the device-disciplined tier:

1. no calls to the process-default mutators (``configure_layout_build``
   / ``configure_sync_timers`` /
   ``set_layout_build_mode`` / ``timer.set_sync_mode``) — those belong to
   offline entry points (tools, bench), never to pipeline code;
2. no direct ``jax.config.update("jax_compilation_cache...")`` — the
   cache directory is a deployment setting fixed once at import
   (``kaminpar_tpu/__init__.py``);
3. no reads of the module-level defaults (``_layout_build_mode``) — resolve
   through ``resolve_layout_build_mode`` / ``current_runtime()``;
4. every locally constructed ``CSRGraph`` / ``from_numpy_csr`` graph must
   pin ``_layout_mode`` before it escapes the function — the construction
   site is the only place that still knows which engine owns the graph once
   the work lands on a pool worker (the exact PR 6 escape).
"""

from __future__ import annotations

import ast
from typing import List

from ..core import Finding, LintConfig, Rule, SourceModule
from ._walk import iter_scopes, walk_scope

_BANNED_CALL_SUFFIXES = (
    "context.configure_layout_build",
    "context.configure_sync_timers",
    "csr.set_layout_build_mode",
    "timer.set_sync_mode",
)
_BANNED_CALL_NAMES = (
    "configure_layout_build",
    "configure_sync_timers",
    "set_layout_build_mode",
    "set_sync_mode",
)
_BANNED_GLOBALS = ("_layout_build_mode",)
_GRAPH_CONSTRUCTORS = ("from_numpy_csr", "CSRGraph")


def _assignment_parts(node: ast.AST):
    """(targets, value) of Assign/AnnAssign nodes, else ([], None)."""
    if isinstance(node, ast.Assign):
        return node.targets, node.value
    if isinstance(node, ast.AnnAssign) and node.value is not None:
        return [node.target], node.value
    return [], None


def _target_path(node: ast.AST):
    """Dotted path of a Name/Attribute chain ("g", "self.g"); None for
    anything else (subscripts, calls)."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if not isinstance(node, ast.Name):
        return None
    parts.append(node.id)
    return ".".join(reversed(parts))


class RuntimeIsolationRule(Rule):
    name = "runtime-isolation"
    description = (
        "pipeline code must reach layout/sync settings through the "
        "active EngineRuntime, and never reconfigure the process"
    )

    def check(self, mod: SourceModule, config: LintConfig) -> List[Finding]:
        if not config.is_device_module(mod):
            return []
        out: List[Finding] = []
        self._check_banned(mod, out)
        for scope, body in iter_scopes(mod.tree):
            if isinstance(scope, ast.Module):
                continue
            self._check_graph_pins(scope, mod, out)
        return out

    # -- banned process-default access --------------------------------------

    def _check_banned(self, mod: SourceModule, out: List[Finding]) -> None:
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.Call):
                qual = mod.imports.qualname(node.func) or ""
                leaf = qual.rsplit(".", 1)[-1]
                if qual.endswith(_BANNED_CALL_SUFFIXES) or leaf in _BANNED_CALL_NAMES:
                    out.append(self.finding(
                        mod, node,
                        f"{leaf}() mutates a process default — pipeline "
                        "code must own settings through its EngineRuntime "
                        "(context.current_runtime() / activate()), not "
                        "reconfigure the process",
                    ))
                elif (
                    qual == "jax.config.update"
                    and node.args
                    and isinstance(node.args[0], ast.Constant)
                    and isinstance(node.args[0].value, str)
                    and node.args[0].value.startswith(
                        ("jax_compilation_cache", "jax_persistent_cache")
                    )
                ):
                    out.append(self.finding(
                        mod, node,
                        "direct compilation-cache config mutation — the "
                        "cache directory is fixed once at import "
                        "(kaminpar_tpu/__init__.py)",
                    ))
            elif isinstance(node, (ast.Name, ast.Attribute)):
                name = node.id if isinstance(node, ast.Name) else node.attr
                if name in _BANNED_GLOBALS and isinstance(
                    getattr(node, "ctx", None), ast.Load
                ):
                    out.append(self.finding(
                        mod, node,
                        f"direct read of the process default {name!r} — "
                        "resolve through csr.resolve_layout_build_mode() "
                        "(which consults the active EngineRuntime first)",
                    ))

    # -- per-graph layout pin (the PR 6 escape) -----------------------------

    def _check_graph_pins(
        self, func: ast.AST, mod: SourceModule, out: List[Finding]
    ) -> None:
        """Within one function: every target assigned from a graph
        constructor (a plain name, an attribute like ``self.g``, or an
        annotated assignment) must have ``<target>._layout_mode`` stored
        somewhere in the same function body."""
        pinned = set()
        constructed = {}  # target path -> construction Call node
        for node in walk_scope(func):
            targets, value = _assignment_parts(node)
            if value is None:
                continue
            if isinstance(value, ast.Call):
                qual = mod.imports.qualname(value.func) or ""
                if qual.rsplit(".", 1)[-1] in _GRAPH_CONSTRUCTORS:
                    for t in targets:
                        path = _target_path(t)
                        if path:
                            constructed[path] = value
            for t in targets:
                if isinstance(t, ast.Attribute) and t.attr == "_layout_mode":
                    base = _target_path(t.value)
                    if base:
                        pinned.add(base)
        for path, call in constructed.items():
            if path not in pinned:
                out.append(self.finding(
                    mod, call,
                    f"graph {path!r} constructed without an explicit "
                    "_layout_mode pin: on a thread-pool worker the "
                    "engine's thread-local EngineRuntime activation is "
                    "invisible and resolution silently falls through to "
                    "the process default (the PR 6 _nested_partition "
                    "escape) — pin from the owning context or parent graph",
                ))
        # constructions that escape without ever being named cannot be
        # pinned at all
        bound_calls = {id(c) for c in constructed.values()}
        for node in walk_scope(func):
            if isinstance(node, ast.Call) and id(node) not in bound_calls:
                qual = mod.imports.qualname(node.func) or ""
                if qual.rsplit(".", 1)[-1] in _GRAPH_CONSTRUCTORS:
                    out.append(self.finding(
                        mod, node,
                        "graph constructed inline (never bound to a name) "
                        "cannot carry a _layout_mode pin — assign it, pin "
                        "the owning engine's layout mode, then use it",
                    ))
