"""Rule: no blocking calls inside ``async def`` bodies.

The controller and every nodelet are single asyncio loops; one blocking
call in a handler stalls heartbeats, leases, WAL replication, and every
other handler behind it (the actor-scheduler busy-spin of PR 8 and the
565 ms ``wait_actor`` parks once measured are the cost).  This
rule walks every ``async def`` (skipping nested sync ``def``/``lambda``
bodies, which usually run off-loop via ``to_thread``/executors) and
flags:

* ``time.sleep`` — use ``asyncio.sleep``
* sync file I/O: builtin ``open``, ``os.fsync``/any ``.fsync()``
* blocking subprocess calls (``subprocess.run``/``Popen``/…)
* blocking socket construction (``socket.create_connection``)
* unbounded lock acquisition: a non-awaited ``.acquire()`` with no
  ``timeout=``/``blocking=False`` (an awaited ``asyncio.Lock.acquire``
  is fine)
* known-blocking ray_tpu helpers: ``self._p`` / ``*.pstore.append``
  (WAL append + fsync), ``spill.write_object``/``spill.delete_file``
  (sync disk), ``EventLoopThread.run`` via ``*._lt.run`` (cross-thread
  join — deadlock bait on the loop)
"""

from __future__ import annotations

import ast
from typing import List

from ..engine import Finding, LintContext, Rule

#: exact dotted-name matches
_BLOCKING_EXACT = {
    "time.sleep": "time.sleep() blocks the event loop; use "
                  "`await asyncio.sleep(...)`",
    "open": "sync file I/O on the event loop; use "
            "`await asyncio.to_thread(...)` (or accept + baseline)",
    "os.fsync": "fsync on the event loop stalls every handler behind "
                "the disk",
    "subprocess.run": "blocking subprocess call on the event loop",
    "subprocess.call": "blocking subprocess call on the event loop",
    "subprocess.check_call": "blocking subprocess call on the event "
                             "loop",
    "subprocess.check_output": "blocking subprocess call on the event "
                               "loop",
    "subprocess.Popen": "fork/exec on the event loop (milliseconds "
                        "under load); prefer to_thread or the zygote "
                        "path",
    "socket.create_connection": "blocking connect on the event loop; "
                                "use asyncio.open_connection",
}

#: dotted-name suffix matches (obj resolved or not)
_BLOCKING_SUFFIX = {
    ".fsync": "fsync on the event loop stalls every handler behind "
              "the disk",
    "._p": "WAL append (+fsync) runs synchronously on the controller "
           "loop",
    ".pstore.append": "WAL append (+fsync) runs synchronously on the "
                      "controller loop",
    "._lt.run": "cross-thread join back into an event loop; "
                "deadlocks if called from that loop",
    "spill.write_object": "sync disk write on the event loop; wrap in "
                          "asyncio.to_thread",
    "spill.delete_file": "sync disk unlink on the event loop; wrap in "
                         "asyncio.to_thread",
}


def _short(dotted: str) -> str:
    return dotted.lstrip("?.") or "?"


class LoopBlockingRule(Rule):
    id = "loop-blocking"

    def visit_file(self, rel: str, tree: ast.AST, lines, ctx:
                   LintContext) -> List[Finding]:
        findings: List[Finding] = []
        for node in ast.walk(tree):
            if isinstance(node, ast.AsyncFunctionDef):
                scope = node.name
                self._scan_async_body(rel, scope, node.body, findings)
        return findings

    # ------------------------------------------------------------ internals
    def _scan_async_body(self, rel: str, scope: str, body, findings,
                         awaited_calls=None) -> None:
        for stmt in body:
            self._scan_node(rel, scope, stmt, findings, awaited=False)

    def _scan_node(self, rel: str, scope: str, node: ast.AST, findings,
                   awaited: bool) -> None:
        # nested sync defs / lambdas usually execute off-loop
        # (to_thread, executors, callbacks) — skip their bodies; a
        # nested *async* def is picked up by visit_file's own walk
        # under its own scope name
        if isinstance(node, (ast.FunctionDef, ast.Lambda,
                             ast.AsyncFunctionDef)):
            return
        if isinstance(node, ast.Await):
            self._scan_node(rel, scope, node.value, findings,
                            awaited=True)
            return
        if isinstance(node, ast.Call):
            self._check_call(rel, scope, node, findings, awaited)
            # calls composed into an awaited wrapper (e.g. `await
            # asyncio.wait_for(lock.acquire(), ...)`) inherit the await
            for child in ast.iter_child_nodes(node):
                self._scan_node(rel, scope, child, findings,
                                awaited=awaited)
            return
        for child in ast.iter_child_nodes(node):
            self._scan_node(rel, scope, child, findings, awaited=False)

    def _check_call(self, rel: str, scope: str, call: ast.Call,
                    findings, awaited: bool) -> None:
        dotted = self.dotted(call.func)
        if not dotted:
            return
        msg = _BLOCKING_EXACT.get(dotted)
        detail = dotted
        if msg is None:
            for suffix, m in _BLOCKING_SUFFIX.items():
                if dotted.endswith(suffix):
                    msg, detail = m, _short(suffix)
                    break
        if msg is None and dotted.endswith(".acquire") and not awaited:
            kwargs = {kw.arg for kw in call.keywords}
            has_bound = bool({"timeout", "blocking"} & kwargs) \
                or len(call.args) >= 1
            if not has_bound:
                msg = ("unbounded lock.acquire() on the event loop; "
                       "pass a timeout, use blocking=False, or take "
                       "the lock off-loop")
                detail = _short(dotted)
        if msg is None:
            return
        findings.append(Finding(
            self.id, rel, call.lineno, scope, detail,
            f"`{_short(dotted)}(...)` inside `async def {scope}`: "
            f"{msg}"))
