"""Rule: shared-state race heuristic for thread-spawning classes.

Classes like ``ContinuousBatchingEngine``, ``ElasticSnapshotter``, and
the HA replication machinery run a background thread over ``self``.
The contract that keeps them honest is simple: instance attributes the
thread mutates are either private to the thread or touched only under
the instance lock.  This rule checks it structurally:

* a class "spawns a thread" when any method constructs
  ``threading.Thread(target=self.<m>, ...)`` — ``<m>`` is the thread
  entry; the thread context is its transitive ``self.*()`` call
  closure within the class, taken from the engine's shared call graph
  (``LintContext.graphs`` — PR-14 generalized the closure this rule
  used to compute privately).
* "instance locks" are attributes assigned ``threading.Lock()`` /
  ``RLock()`` / ``Condition()`` (any dotted spelling), or an instance
  of a class of the repo's own whose name ends in ``Lock`` (a wrapper
  that takes one of those its own way: ``serve/decode_session.py``
  ``_LoopLock``).
* a mutation (``self.x = ...`` / ``self.x += ...``) counts as locked
  when lexically inside ``with self.<lock>:`` — or when the enclosing
  method's name ends in ``_locked`` (the repo convention for
  "caller holds the lock").
* FLAG an attribute that is mutated without the lock in the thread
  context while any public method (no leading underscore) also reads
  or writes it — and symmetrically, mutated without the lock in a
  public method while the thread context touches it.

``__init__`` is exempt (construction happens-before the thread).  This
is a heuristic: atomic-in-CPython counters and benign monotonic flags
will fire — suppress with ``# rtpu: allow[thread-race]`` at the
mutation site or baseline them with a reason.
"""

from __future__ import annotations

import ast
from typing import Dict, List, Optional, Set

from ..engine import Finding, LintContext, Rule

_LOCK_FACTORIES = ("Lock", "RLock", "Condition", "Semaphore",
                   "BoundedSemaphore")
_EXEMPT_METHODS = {"__init__", "__new__", "__del__", "__init_subclass__"}


class _MethodInfo:
    def __init__(self, name: str, line: int):
        self.name = name
        self.line = line
        self.mutated_locked: Set[str] = set()
        self.mutated_unlocked: Dict[str, int] = {}   # attr -> line
        self.reads: Set[str] = set()


class ThreadRaceRule(Rule):
    id = "thread-race"

    def visit_file(self, rel: str, tree: ast.AST, lines, ctx:
                   LintContext) -> List[Finding]:
        findings: List[Finding] = []
        for node in ast.walk(tree):
            if isinstance(node, ast.ClassDef):
                findings.extend(self._check_class(rel, node, ctx))
        return findings

    # ------------------------------------------------------------ per class
    def _check_class(self, rel: str, cls: ast.ClassDef,
                     ctx: LintContext) -> List[Finding]:
        methods: Dict[str, _MethodInfo] = {}
        lock_attrs: Set[str] = set()
        thread_targets: Set[str] = set()
        for item in cls.body:
            if not isinstance(item, (ast.FunctionDef,
                                     ast.AsyncFunctionDef)):
                continue
            info = _MethodInfo(item.name, item.lineno)
            methods[item.name] = info
            self._scan_method(item, info, lock_attrs, thread_targets)
        if not thread_targets:
            return []

        # thread context: entry methods + transitive self-call closure
        # (from the engine's shared call graph)
        graph = ctx.graphs.get(rel)
        thread_ctx: Set[str] = graph.method_closure_names(
            cls.name, [m for m in thread_targets if m in methods]) \
            if graph is not None else set(thread_targets)
        thread_ctx &= set(methods)

        public = [m for m in methods
                  if not m.startswith("_") and m not in thread_ctx]
        findings: List[Finding] = []
        reported: Set[str] = set()
        for side_a, side_b, flip in ((thread_ctx, public, False),
                                     (public, thread_ctx, True)):
            for m in side_a:
                info = methods.get(m)
                if info is None or m in _EXEMPT_METHODS:
                    continue
                for attr, line in sorted(info.mutated_unlocked.items()):
                    if attr in reported:
                        continue
                    touched = [o for o in side_b
                               if o in methods and attr in
                               (methods[o].reads
                                | methods[o].mutated_locked
                                | set(methods[o].mutated_unlocked))]
                    if not touched:
                        continue
                    reported.add(attr)
                    who = "public method" if flip else "thread context"
                    other = ("thread context" if flip
                             else "public method(s)")
                    findings.append(Finding(
                        self.id, rel, line, f"{cls.name}.{m}", attr,
                        f"`self.{attr}` mutated in {who} "
                        f"`{cls.name}.{m}` without the instance lock "
                        f"({self._lock_hint(lock_attrs)}) while "
                        f"{other} {sorted(touched)} also touch it — "
                        f"take the lock, rename the method "
                        f"`*_locked` if the caller holds it, or "
                        f"suppress if the access is benign"))
        return findings

    @staticmethod
    def _lock_hint(lock_attrs: Set[str]) -> str:
        if lock_attrs:
            return "self." + " / self.".join(sorted(lock_attrs))
        return "no lock attribute found on this class"

    # ----------------------------------------------------------- per method
    def _scan_method(self, fn, info: _MethodInfo, lock_attrs: Set[str],
                     thread_targets: Set[str]) -> None:
        convention_locked = fn.name.endswith("_locked")
        self._scan_block(fn.body, info, lock_attrs, thread_targets,
                         locked=convention_locked)

    def _scan_block(self, body, info: _MethodInfo,
                    lock_attrs: Set[str], thread_targets: Set[str],
                    locked: bool) -> None:
        for node in body:
            self._scan_stmt(node, info, lock_attrs, thread_targets,
                            locked)

    def _scan_stmt(self, node, info, lock_attrs, thread_targets,
                   locked: bool) -> None:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.Lambda, ast.ClassDef)):
            return  # nested scopes analyzed separately / skipped
        if isinstance(node, (ast.With, ast.AsyncWith)):
            takes_lock = any(self._is_self_lock(it.context_expr,
                                                lock_attrs)
                             for it in node.items)
            for it in node.items:
                self._scan_expr(it.context_expr, info, lock_attrs,
                                thread_targets)
            self._scan_block(node.body, info, lock_attrs,
                             thread_targets, locked or takes_lock)
            return
        if isinstance(node, (ast.Assign, ast.AugAssign, ast.AnnAssign)):
            targets = node.targets if isinstance(node, ast.Assign) \
                else [node.target]
            for t in targets:
                attr = self._self_attr(t)
                if attr is not None:
                    if self._is_lock_factory(getattr(node, "value",
                                                     None)):
                        lock_attrs.add(attr)
                    if locked:
                        info.mutated_locked.add(attr)
                    else:
                        info.mutated_unlocked.setdefault(attr,
                                                         t.lineno)
            value = getattr(node, "value", None)
            if value is not None:
                self._scan_expr(value, info, lock_attrs,
                                thread_targets)
            if isinstance(node, ast.AugAssign):
                # `self.x += 1` also reads self.x — already recorded
                # as a mutation, which is the stronger fact
                pass
            return
        # generic: record reads + self-calls, then recurse statements
        # (except handlers / match cases are statement containers too)
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.stmt, ast.excepthandler)) \
                    or child.__class__.__name__ == "match_case":
                self._scan_stmt(child, info, lock_attrs,
                                thread_targets, locked)
            else:
                self._scan_expr(child, info, lock_attrs,
                                thread_targets)

    def _scan_expr(self, node, info, lock_attrs, thread_targets) -> None:
        if node is None or isinstance(node, (ast.FunctionDef,
                                             ast.AsyncFunctionDef,
                                             ast.Lambda,
                                             ast.ClassDef)):
            return
        for sub in ast.walk(node):
            if isinstance(sub, ast.Call):
                dotted = self.dotted(sub.func)
                if dotted.endswith("Thread") and "hread" in dotted:
                    for kw in sub.keywords:
                        if kw.arg == "target":
                            tgt = self._self_attr(kw.value)
                            if tgt is not None:
                                thread_targets.add(tgt)
            attr = self._self_attr(sub)
            if attr is not None and isinstance(getattr(sub, "ctx",
                                                       None), ast.Load):
                info.reads.add(attr)

    # ------------------------------------------------------------- helpers
    @staticmethod
    def _self_attr(node) -> Optional[str]:
        if isinstance(node, ast.Attribute) \
                and isinstance(node.value, ast.Name) \
                and node.value.id == "self":
            return node.attr
        return None

    def _is_self_lock(self, expr, lock_attrs: Set[str]) -> bool:
        attr = self._self_attr(expr)
        return attr is not None and attr in lock_attrs

    def _is_lock_factory(self, value) -> bool:
        if not isinstance(value, ast.Call):
            return False
        dotted = self.dotted(value.func)
        name = dotted.split(".")[-1]
        return name in _LOCK_FACTORIES or name.endswith("Lock")
