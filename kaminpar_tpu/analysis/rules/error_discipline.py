"""error-discipline: failures route through the resilience hierarchy.

Round 17 (ISSUE 13): every pipeline/serve failure is classified into the
typed hierarchy of :mod:`kaminpar_tpu.resilience.errors` by the ONE
classifier, so breakers, the degradation ladder, and operators share a
vocabulary.  This rule keeps the discipline from eroding:

1. **No bare ``raise RuntimeError``** in device-disciplined modules — a
   classified failure class hidden inside an untyped RuntimeError is
   invisible to breakers and retry policies; raise the typed error (or a
   :class:`~kaminpar_tpu.serve.errors.ServeError` subclass for
   admission/lifecycle outcomes).
2. **No laundering a caught failure into a bare ValueError/RuntimeError**:
   inside an ``except`` handler that catches a broad type, constructing a
   bare ``ValueError``/``RuntimeError`` discards the failure class.
   (Plain argument-validation ``raise ValueError`` outside handlers stays
   legal — config errors are not failure classes.)
3. **Dispatch-site handlers must classify**: a ``try`` whose body calls a
   dispatch callee (``compute_partition``, ``run_lanestacked``,
   ``pool_bipartition_device``, ...) and whose handler catches
   ``Exception``/``BaseException``/bare must route through
   ``resilience.errors.classify`` (or construct a typed resilience
   error, or re-raise) — the round-11-era ``ServeError(f"batch failed:
   {exc!r}")`` pattern this rule exists to retire.
"""

from __future__ import annotations

import ast
from typing import List, Optional

from ..core import Finding, LintConfig, Rule, SourceModule

_BROAD = {"Exception", "BaseException", "RuntimeError"}
_TYPED = {
    "CompileTimeout", "ExecuteFault", "CapacityExceeded",
    "BackendUnavailable", "PoisonedCell", "WorkerHung",
    "GraphValidationError", "ResilienceError",
}
_BARE = {"RuntimeError", "ValueError"}
_DEFAULT_DISPATCH_CALLEES = (
    "compute_partition", "run_lanestacked", "pool_bipartition_device",
    "_device_bipartition", "_execute_batch", "batched_metrics",
)
_CLASSIFY_QUAL = "kaminpar_tpu.resilience.errors.classify"


def _callee_name(call: ast.Call) -> Optional[str]:
    if isinstance(call.func, ast.Attribute):
        return call.func.attr
    if isinstance(call.func, ast.Name):
        return call.func.id
    return None


def _catches_broad(handler: ast.ExceptHandler) -> bool:
    if handler.type is None:
        return True
    types = (
        handler.type.elts
        if isinstance(handler.type, ast.Tuple)
        else [handler.type]
    )
    for t in types:
        name = t.attr if isinstance(t, ast.Attribute) else (
            t.id if isinstance(t, ast.Name) else None
        )
        if name in _BROAD:
            return True
    return False


class ErrorDisciplineRule(Rule):
    name = "error-discipline"
    description = (
        "pipeline/serve failures route through the resilience hierarchy: "
        "no bare RuntimeError raises, no laundering caught failures into "
        "untyped errors, dispatch-site except handlers must call "
        "resilience.errors.classify"
    )

    def _classifies(self, mod: SourceModule, handler: ast.ExceptHandler) -> bool:
        for node in ast.walk(handler):
            if isinstance(node, ast.Raise) and node.exc is None:
                return True  # bare re-raise keeps the original type
            if not isinstance(node, ast.Call):
                continue
            name = _callee_name(node)
            if name in _TYPED:
                return True
            if name == "classify":
                qual = mod.imports.qualname(node.func) or ""
                if qual == _CLASSIFY_QUAL or qual.endswith(".classify"):
                    return True
        return False

    def check(self, mod: SourceModule, config: LintConfig) -> List[Finding]:
        if not config.is_device_module(mod):
            return []
        opts = config.options(self.name)
        callees = set(opts.get("dispatch_callees", _DEFAULT_DISPATCH_CALLEES))
        out: List[Finding] = []

        # Map every node inside an except handler to its handler for the
        # laundering check (rule 2).
        handler_of = {}
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.ExceptHandler):
                for sub in ast.walk(node):
                    handler_of.setdefault(id(sub), node)

        for node in ast.walk(mod.tree):
            if isinstance(node, ast.Raise) and isinstance(node.exc, ast.Call):
                name = _callee_name(node.exc)
                handler = handler_of.get(id(node))
                if name == "RuntimeError":
                    out.append(self.finding(
                        mod, node,
                        "bare RuntimeError in a pipeline/serve module — "
                        "raise the typed resilience error "
                        "(kaminpar_tpu/resilience/errors.py) so breakers "
                        "and retry policies see the failure class",
                    ))
                elif (
                    name in _BARE
                    and handler is not None
                    and _catches_broad(handler)
                ):
                    out.append(self.finding(
                        mod, node,
                        f"caught failure laundered into a bare {name} — "
                        "route through resilience.errors.classify (the "
                        "failure class must survive the handler)",
                    ))
            elif isinstance(node, ast.Try):
                has_dispatch = any(
                    isinstance(sub, ast.Call) and _callee_name(sub) in callees
                    for stmt in node.body
                    for sub in ast.walk(stmt)
                )
                if not has_dispatch:
                    continue
                for handler in node.handlers:
                    if not _catches_broad(handler):
                        continue
                    if self._classifies(mod, handler):
                        continue
                    out.append(self.finding(
                        mod, handler,
                        "broad except around a dispatch site does not "
                        "route through the resilience classifier — call "
                        "resilience.errors.classify(exc, site=...) (or "
                        "construct a typed resilience error / re-raise) "
                        "so the failure class reaches breakers and "
                        "callers",
                    ))
        return out
