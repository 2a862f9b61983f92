"""Deterministic fault injection (chaos) layer.

Failure is a first-class, *seeded* test input: a fault **plan** is a JSON
list of rules

    {"site": "rpc.send",                 # where to inject
     "match": {"nth": 3} | {"prob": 0.1, "seed": 7} | {"regex": "hb.*"}
              | {"peer": "ab12"},        # peer-directed sites only: fire
                                         #   only toward matching peers —
                                         #   severs A→B while B→A works
     "action": "drop",                   # what to do (site-dependent)
     "delay_s": 0.05,                    # for delay/latency + kill delays
     "once": true,                       # fire once CLUSTER-wide (claimed
                                         #   through the controller)
     "max_fires": 2,                     # per-process fire cap
     "proc": "worker"}                   # only in this process kind; a
                                         #   "nodelet:<node-id-prefix>"
                                         #   form pins the rule to ONE
                                         #   node's process (asymmetric
                                         #   partitions need a side)

distributed to every process via the controller KV (namespace ``chaos``,
pubsub channel ``chaos``, ``ray-tpu chaos apply``) or armed at bootstrap
from the ``chaos_plan`` config flag (``RAY_TPU_CHAOS_PLAN``), which the
existing config propagation ships to every spawned process.

Matchers are deterministic: ``nth`` fires on the Nth *eligible* hit of
the site in this process (regex filters which calls count as hits);
``prob`` draws from a per-rule ``random.Random(seed)`` whose sequence
replays identically run-to-run; ``regex`` matches the site key (RPC
method, function name, deployment name, object id hex).

Known sites (threaded through the runtime):

==========================  =====================================================
site                        actions
==========================  =====================================================
``rpc.send``                ``drop`` (frame lost), ``delay``, ``sever`` (close
                            the connection), ``error`` (raise RpcError)
``rpc.connect``             ``error``/``drop`` (connect refused), ``delay``
``nodelet.lease``           ``kill_worker`` (kill the just-granted worker after
                            ``delay_s`` — a gang/task worker dying mid-step)
``nodelet.heartbeat``       any action blackholes that heartbeat (partition)
``object.fetch_meta``       ``evict`` (drop the local copy + directory entry —
                            forces lineage reconstruction at the puller)
``worker.before_put``       ``crash`` (exit before the result reaches the
                            store: the task retries and re-executes),
                            ``delay``, ``error``
``worker.after_put``        same, after the result put (retry must be
                            idempotent against the already-stored object)
``worker.exec_crash``       ``sigkill``/``sigsegv``/``sigabrt`` signal-
                            kills the worker at task execution start
                            (key: function name) — a REAL signal death,
                            so the nodelet's death attributor classifies
                            it poison-shaped and the controller's crash
                            ledger counts it (the poison-wave e2e's
                            weapon); ``crash``/``error`` behave like the
                            ``worker.before_put`` variants
``nodelet.death_classify``  any action degrades the nodelet's death
                            attribution for that worker death (key:
                            worker id hex) to cause ``unknown`` —
                            proves the containment layer fails safe
                            when the classifier itself is attacked
                            (unknown is conservatively poison-shaped)
``serve.request``           ``crash`` (replica dies mid-request), ``error``,
                            ``delay``/``latency``
``serve.health_check``      ``error`` (health check fails)
``serve.decode_step``      ``error`` fails the read of a fused decode step's
                            tokens, where an asynchronous device fault
                            surfaces (serve/decode_session.py): the step
                            queued behind it goes with it, every session
                            that holds a slot fails once and the engine
                            serves on from a fresh cache; ``delay``
                            stretches the read
``serve.prefill_chunk``     ``error`` fails the chunk program that advances
                            several joining sessions at once (the lanes
                            program, serve/decode_session.py): the sessions
                            in it fail, each with its own error, the lane
                            cache goes with them, and those that waited for
                            a lane prefill on; ``delay`` stretches it
``serve.session_failover``  attacks decode-stream RECOVERY itself
                            (serve/failover.py): ``error`` fails the
                            resume (the stream surfaces the in-band
                            error the failover would have hidden),
                            ``delay`` stretches the client-visible stall
``drain.evacuate``          any action fails that object's evacuation during a
                            node drain (the object rides the node to its death
                            and must come back via lineage reconstruction)
``drain.deadline``          any action forces the drain orchestrator to treat
                            the drain as deadline-overrun — the node takes the
                            hard-death recovery path immediately
``train.snapshot_put``      ``error``/``fail`` loses that elastic train
                            snapshot (the previous one stands — a repair's
                            lost-steps window widens by one interval),
                            ``delay`` stretches the off-step-path put
``train.repair_restore``    attacks elastic gang REPAIR itself
                            (train/backend_executor.py): ``error``/``fail``
                            aborts the repair — the run must take the
                            legacy full-restart-from-disk fallback;
                            ``delay`` stretches the repair window (the
                            double-failure tests land a second kill inside
                            it)
``controller.wal_replicate`` attacks the leader→standby WAL stream
                            (core/ha.py): ``drop`` loses a record batch
                            (the seq gap forces a snapshot resync; sync-
                            mode writes degrade to bounded-lag async
                            instead of stalling), ``delay`` stretches the
                            replication lag
``controller.lease_renew``  any action blackholes one leader→standby
                            lease renewal — enough in a row and the
                            standby promotes itself (forced failover
                            under a live TCP connection)
``object.transfer_fetch``   any action fails that cross-node object
                            fetch attempt at the PULLING nodelet (native
                            and chunked paths both) — with a ``peer``
                            matcher + a ``proc`` node pin this severs
                            the A→B transfer path only, driving the
                            alternate-path fetch ladder (retry →
                            alt copy → relay → lineage)
``nodelet.peer_probe``      any action makes that peer-reachability
                            probe report the peer unreachable — feeds
                            false negatives into the connectivity
                            matrix the suspect/quarantine logic folds
``controller.admission_shed`` ``force`` sheds the matched op (typed
                            ``_overload`` pushback) regardless of the
                            watermark state, ``suppress`` admits it even
                            under brownout — key is the op name.
                            Liveness-lane ops are never shed, forced or
                            not (core/overload.py pins the invariant)
``rpc.lane_starve``         ``delay``/``latency`` holds dispatch of
                            ONE priority lane (key: ``liveness`` |
                            ``control`` | ``bulk``) at the receiving
                            connection; a persistent rule THROTTLES
                            the lane to one dispatch per ``delay_s``
                            (an expired hold admits one item before
                            chaos re-evaluates) — proves the other
                            lanes keep flowing past a wedged one
``wal.append``              filesystem domain (key ``<dirname>:<op>``):
                            ``enospc``/``eio``/``error`` fails the WAL
                            record write — the store poisons itself and
                            the leader must self-fence (fsyncgate: after
                            one failed write the durable state is
                            unknowable)
``wal.fsync``               same, at the per-append fsync; ``delay`` is
                            a BLOCKING fsync stall (a dying disk hangs,
                            it does not return)
``wal.snapshot``            fails the compaction snapshot's tmp-write /
                            replace / dir-fsync dance — the WAL must
                            survive intact and replay
``spill.write``             ``enospc``/``eio``/``error`` fails that
                            object spill write (key: object id hex) —
                            proactive spill skips the object (it stays
                            in memory), capacity-pressure spill degrades
                            to in-memory retention + put backpressure
``spill.restore``           fails/corrupts that spill read — the copy is
                            treated as missing and the fetch ladder
                            falls through to alternates/lineage
``spill.delete``            fails the spill-file GC unlink (leaked file,
                            never a correctness fault)
``train.checkpoint_register`` fails the checkpoint commit dance
                            (train/checkpointing.py): the previous
                            checkpoint must stay loadable and the
                            caller gets a typed CheckpointWriteError
``flight.write``            fails the flight-recorder bundle write —
                            incident capture is best-effort: shed with
                            a counter, never an operator-visible error
==========================  =====================================================

Peer-directed sites (``rpc.send``, ``object.transfer_fetch``,
``nodelet.peer_probe``) evaluate an optional ``match.peer`` regex
against the remote side's label (dialed ``host:port`` for RPC, peer
node id for transfer/probe) — a rule can sever the A→B direction of a
link while B→A keeps working, the asymmetric partitions real networks
produce.

Zero-cost when disabled: every hot path guards with one module-level
``None`` check (``fi.ACTIVE is not None``, or the ``_chaos`` hook the
arm() call injects into ``core.rpc``/``core.worker_runtime``, which
cannot import this package at module scope without a cycle).  Every
injected fault increments ``ray_tpu_chaos_injected_total{site,action}``
(the counter is registered only while the layer is armed, so a clean
cluster's metrics never mention it) and records a ``chaos`` trace span
so the cluster timeline shows the fault *and* the recovery around it.
"""

from __future__ import annotations

import json
import random
import re
import sys
import threading
import time
from typing import Any, Dict, List, Optional

from ..core.config import GlobalConfig
from . import tracing

CHAOS_KV_NS = "chaos"
CHAOS_KV_KEY = b"plan"
METRIC_NAME = "ray_tpu_chaos_injected_total"
CRASH_EXIT_CODE = 170  # distinguishable from user exits in worker logs

#: Filesystem sites all speak the same action set; error is a generic
#: injected OSError, enospc/eio carry the matching errno so callers'
#: errno-discriminating paths are exercised.
_FS_ACTIONS = frozenset({"error", "enospc", "eio"})

#: Every injection site threaded through the runtime, with the actions
#: that site understands (None = any action blackholes/fails the site).
#: ``delay``/``latency`` are universally valid.  `ray-tpu chaos
#: validate` lints plans against this registry so a typoed site or
#: action fails FAST instead of silently never firing.
KNOWN_SITES: Dict[str, Optional[frozenset]] = {
    "rpc.send": frozenset({"drop", "sever", "error"}),
    "rpc.connect": frozenset({"error", "drop"}),
    "nodelet.lease": frozenset({"kill_worker"}),
    "nodelet.heartbeat": None,
    "object.fetch_meta": frozenset({"evict"}),
    "worker.before_put": frozenset({"crash", "error"}),
    "worker.after_put": frozenset({"crash", "error"}),
    "worker.exec_crash": frozenset({"sigkill", "sigsegv", "sigabrt",
                                    "crash", "error"}),
    "nodelet.death_classify": None,
    "serve.request": frozenset({"crash", "error", "fail"}),
    "serve.health_check": frozenset({"error", "fail"}),
    "serve.session_failover": frozenset({"error", "fail"}),
    "serve.autoscale": frozenset({"drop", "error", "fail"}),
    "serve.decode_step": frozenset({"error", "fail"}),
    "serve.prefill_chunk": frozenset({"error", "fail"}),
    "serve.slo_eval": frozenset({"error", "fail"}),
    "drain.evacuate": None,
    "drain.deadline": None,
    "train.snapshot_put": frozenset({"error", "fail"}),
    "train.repair_restore": frozenset({"error", "fail"}),
    "controller.wal_replicate": frozenset({"drop"}),
    "controller.lease_renew": None,
    "object.transfer_fetch": None,
    "nodelet.peer_probe": None,
    "controller.admission_shed": frozenset({"force", "suppress"}),
    "rpc.lane_starve": frozenset(),
    # Filesystem fault domain: error/enospc/eio raise OSError at the
    # site (delay/latency = a blocking stall — a dying disk hangs).
    "wal.append": _FS_ACTIONS,
    "wal.fsync": _FS_ACTIONS,
    "wal.snapshot": _FS_ACTIONS,
    "spill.write": _FS_ACTIONS,
    "spill.restore": _FS_ACTIONS,
    "spill.delete": _FS_ACTIONS,
    "train.checkpoint_register": _FS_ACTIONS,
    "flight.write": _FS_ACTIONS,
}
_UNIVERSAL_ACTIONS = frozenset({"delay", "latency"})
_RULE_KEYS = frozenset({"site", "action", "match", "delay_s", "once",
                        "max_fires", "proc", "id", "seed"})
_MATCH_KEYS = frozenset({"nth", "prob", "seed", "regex", "peer"})

#: The armed plan, or None when the chaos layer is disabled.  Hot paths
#: outside the import-cycle modules guard with ``fi.ACTIVE is not None``.
ACTIVE: Optional["FaultPlan"] = None

_lock = threading.Lock()
_counter = None            # metrics.Counter, registered only while armed
_local_claims: set = set()  # per-process fallback for `once` rules

# Modules whose hot paths cannot import this package at module scope
# (they sit below ray_tpu.util in the import graph); arm()/disarm() push
# the plan into their `_chaos` module global instead.
_HOOKED_MODULES = ("ray_tpu.core.rpc", "ray_tpu.core.worker_runtime")


class FaultRule:
    def __init__(self, idx: int, d: Dict[str, Any]):
        self.site = d["site"]
        self.action = d["action"]
        m = d.get("match") or {}
        self.nth = m.get("nth")
        self.prob = m.get("prob")
        self.regex = re.compile(m["regex"]) if m.get("regex") else None
        # peer-directed filter: only fire toward matching remote peers
        # (severs one DIRECTION of a link — asymmetric partitions)
        self.peer = re.compile(m["peer"]) if m.get("peer") else None
        self.seed = int(m.get("seed", d.get("seed", 0)))
        self.delay_s = float(d.get("delay_s", 0.05))
        self.max_fires = d.get("max_fires")
        self.once = bool(d.get("once"))
        self.proc = d.get("proc")
        self.rule_id = d.get("id") or f"{self.site}#{idx}"
        self._rng = random.Random(self.seed)
        self.hits = 0
        self.fires = 0

    def matches(self, key: str, proc_kind: str, proc_node: str = "",
                peer: str = "") -> bool:
        """One eligible hit of this rule's site; True when the fault
        fires.  Order matters for determinism: the regex/peer filters
        decide which calls count as hits, then nth/prob decide on the
        hit sequence."""
        if self.proc and not self._proc_matches(proc_kind, proc_node):
            return False
        if self.regex is not None and not self.regex.search(key or ""):
            return False
        if self.peer is not None and not self.peer.search(peer or ""):
            return False
        self.hits += 1
        if self.once and self.fires >= 1:
            return False
        if self.max_fires is not None and self.fires >= self.max_fires:
            return False
        if self.nth is not None:
            wanted = self.nth if isinstance(self.nth, (list, tuple)) \
                else (self.nth,)
            if self.hits not in wanted:
                return False
        if self.prob is not None and self._rng.random() >= self.prob:
            return False
        self.fires += 1
        return True

    def _proc_matches(self, proc_kind: str, proc_node: str) -> bool:
        """``proc`` filter: a bare kind ("nodelet") matches every process
        of that kind; ``"nodelet:<node-id-prefix>"`` pins the rule to
        the process running on ONE node (the tracing identity stores 8
        hex chars, so prefixes compare on their overlap)."""
        if ":" not in self.proc:
            return self.proc == proc_kind
        kind, _, pref = self.proc.partition(":")
        if kind != proc_kind or not pref or not proc_node:
            return False
        return pref.startswith(proc_node) or proc_node.startswith(pref)

    def to_act(self) -> Dict[str, Any]:
        return {"action": self.action, "delay_s": self.delay_s,
                "rule_id": self.rule_id, "once": self.once}


class FaultPlan:
    """Parsed plan; also the object injected into the hooked modules
    (they call ``point``/``async_point`` on it directly)."""

    def __init__(self, rules_json: List[Dict[str, Any]]):
        self.raw = [dict(r) for r in rules_json]
        self.rules: Dict[str, List[FaultRule]] = {}
        for i, d in enumerate(rules_json):
            r = FaultRule(i, d)
            self.rules.setdefault(r.site, []).append(r)

    def point(self, site: str, key: str = "",
              peer: str = "") -> Optional[Dict[str, Any]]:
        """Evaluate the plan at one injection site.  Returns the action
        dict when a rule fires (counting the metric and recording a
        trace span), else None.  Sync and loop-safe.  ``peer`` labels
        the remote side at peer-directed sites (dialed host:port, peer
        node id) for ``match.peer`` rules."""
        rules = self.rules.get(site)
        if not rules:
            return None
        kind = tracing._proc.get("kind", "")
        node = tracing._proc.get("node", "")
        for r in rules:
            with _lock:
                fired = r.matches(key, kind, node, peer)
            if fired:
                _count(site, r.action)
                now = time.time()
                tracing.record_span(f"chaos::{site}", "chaos", now, now,
                                    action=r.action, rule=r.rule_id,
                                    key=key)
                return r.to_act()
        return None

    async def async_point(self, site: str, key: str = "",
                          peer: str = "") -> Optional[Dict[str, Any]]:
        """``point`` for async sites: delay/latency actions sleep here
        (non-blocking); the action dict is returned either way so the
        caller applies drop/sever/error semantics itself."""
        act = self.point(site, key, peer)
        if act is not None and act["action"] in ("delay", "latency"):
            import asyncio
            await asyncio.sleep(max(0.0, act["delay_s"]))
        return act


# ----------------------------------------------------- filesystem domain

def fs_point(site: str, key: str = "") -> None:
    """Evaluate a filesystem chaos site; raises the injected ``OSError``
    (errno per action) or sleeps through a ``delay`` stall.

    Filesystem sites run in sync context (``asyncio.to_thread`` workers,
    the controller's deliberate fsync-per-append path), so the delay is
    a BLOCKING sleep — exactly what a stalling fsync does to its caller.
    """
    if ACTIVE is None:
        return
    act = ACTIVE.point(site, key)
    if act is None:
        return
    if act["action"] in _UNIVERSAL_ACTIONS:
        time.sleep(max(0.0, act["delay_s"]))
        return
    import errno
    import os
    eno = {"enospc": errno.ENOSPC, "eio": errno.EIO}.get(
        act["action"], errno.EIO)
    raise OSError(eno, f"chaos[{act['rule_id']}]: injected "
                       f"{os.strerror(eno)}", key or site)


# ----------------------------------------------------------- arm / disarm

def arm(plan: Any) -> "FaultPlan":
    """Arm the chaos layer in THIS process.  ``plan`` is the rule list
    (or its JSON text).  Re-arming replaces the plan and resets rule
    counters.  The plan is also written into GlobalConfig so processes
    THIS one spawns or registers later inherit it (a nodelet's
    register_worker reply ships its config snapshot — workers forked
    after a runtime `chaos apply` must still arm)."""
    global ACTIVE
    if isinstance(plan, (str, bytes)):
        plan = json.loads(plan)
    fp = FaultPlan(list(plan))
    with _lock:
        ACTIVE = fp
        _ensure_counter()
    try:
        GlobalConfig.update({"chaos_plan": json.dumps(fp.raw)})
    except KeyError:
        pass
    _sync_hooks(fp)
    return fp


def disarm() -> None:
    """Disable the layer and deregister its counter — a disabled cluster's
    metrics must not even mention the chaos metric."""
    global ACTIVE, _counter
    with _lock:
        ACTIVE = None
        if _counter is not None:
            from .. import metrics
            with metrics._lock:
                metrics._registry.pop(METRIC_NAME, None)
            _counter = None
        _local_claims.clear()
    try:
        import os
        GlobalConfig.update({"chaos_plan": ""})
        os.environ.pop("RAY_TPU_CHAOS_PLAN", None)
    except KeyError:
        pass
    _sync_hooks(None)


def maybe_arm_from_config() -> None:
    """Arm from the ``chaos_plan`` config flag (env-propagated to every
    spawned process) — no-op when empty or when already armed (so a late
    lazy CoreClient never resets a live plan's counters)."""
    if ACTIVE is not None:
        return
    raw = getattr(GlobalConfig, "chaos_plan", "") or ""
    if not raw:
        return
    try:
        arm(raw)
    except (ValueError, KeyError) as e:
        print(f"WARNING: ignoring malformed chaos plan: {e}",
              file=sys.stderr, flush=True)


def _sync_hooks(fp: Optional["FaultPlan"]) -> None:
    for name in _HOOKED_MODULES:
        mod = sys.modules.get(name)
        if mod is not None:
            mod._chaos = fp


def plan_snapshot() -> Optional[List[Dict[str, Any]]]:
    return list(ACTIVE.raw) if ACTIVE is not None else None


# ---------------------------------------------------------------- validation

def validate_plan(plan: Any) -> List[str]:
    """Lint a chaos plan; returns human-readable issues (empty = clean).

    A malformed plan mostly fails SILENTLY at runtime — an unknown site
    never fires, a bad regex raises at arm time in every process, two
    ``once`` rules sharing an id starve each other at the claim — so
    `ray-tpu chaos validate <plan.json>` runs these checks up front."""
    issues: List[str] = []
    if not isinstance(plan, list):
        return [f"plan must be a JSON list of rules, got "
                f"{type(plan).__name__}"]
    seen_ids: Dict[str, int] = {}
    for i, d in enumerate(plan):
        tag = f"rule #{i}"
        if not isinstance(d, dict):
            issues.append(f"{tag}: not an object "
                          f"({type(d).__name__})")
            continue
        site = d.get("site")
        if d.get("id"):
            tag = f"rule #{i} ({d['id']!r})"
        elif site:
            tag = f"rule #{i} ({site})"
        for k in d:
            if k not in _RULE_KEYS:
                issues.append(f"{tag}: unknown key {k!r} "
                              f"(known: {', '.join(sorted(_RULE_KEYS))})")
        if not site:
            issues.append(f"{tag}: missing 'site'")
        elif site not in KNOWN_SITES:
            issues.append(
                f"{tag}: unknown site {site!r} — the rule would never "
                f"fire (known: {', '.join(sorted(KNOWN_SITES))})")
        action = d.get("action")
        if not action:
            issues.append(f"{tag}: missing 'action'")
        elif site in KNOWN_SITES:
            allowed = KNOWN_SITES[site]
            if allowed is not None and action not in allowed \
                    and action not in _UNIVERSAL_ACTIONS:
                issues.append(
                    f"{tag}: action {action!r} is a no-op at site "
                    f"{site!r} (understood: "
                    f"{', '.join(sorted(allowed | _UNIVERSAL_ACTIONS))})")
        m = d.get("match")
        if m is not None and not isinstance(m, dict):
            issues.append(f"{tag}: 'match' must be an object")
            m = None
        if m:
            for k in m:
                if k not in _MATCH_KEYS:
                    issues.append(f"{tag}: unknown matcher {k!r} "
                                  f"(known: nth, prob, seed, regex, "
                                  f"peer)")
            if "nth" in m and "prob" in m:
                issues.append(f"{tag}: 'nth' and 'prob' conflict — one "
                              f"rule matches by count OR by draw, not "
                              f"both")
            nth = m.get("nth")
            if nth is not None and not (
                    isinstance(nth, int) and not isinstance(nth, bool)
                    or (isinstance(nth, (list, tuple)) and nth and all(
                        isinstance(n, int) and not isinstance(n, bool)
                        for n in nth))):
                issues.append(f"{tag}: 'nth' must be an int or a "
                              f"non-empty list of ints, got {nth!r}")
            prob = m.get("prob")
            if prob is not None and not (
                    isinstance(prob, (int, float))
                    and not isinstance(prob, bool) and 0 < prob <= 1):
                issues.append(f"{tag}: 'prob' must be in (0, 1], got "
                              f"{prob!r}")
            if m.get("regex") is not None:
                try:
                    re.compile(m["regex"])
                except (re.error, TypeError) as e:
                    issues.append(f"{tag}: bad regex "
                                  f"{m.get('regex')!r}: {e}")
            if m.get("peer") is not None:
                try:
                    re.compile(m["peer"])
                except (re.error, TypeError) as e:
                    issues.append(f"{tag}: bad peer matcher "
                                  f"{m.get('peer')!r}: {e}")
        delay = d.get("delay_s")
        if delay is not None and (not isinstance(delay, (int, float))
                                  or isinstance(delay, bool)
                                  or delay < 0):
            issues.append(f"{tag}: 'delay_s' must be a non-negative "
                          f"number, got {delay!r}")
        mf = d.get("max_fires")
        if mf is not None and (not isinstance(mf, int)
                               or isinstance(mf, bool) or mf < 1):
            issues.append(f"{tag}: 'max_fires' must be a positive int, "
                          f"got {mf!r}")
        if d.get("once") and isinstance(mf, int) and mf > 1:
            issues.append(f"{tag}: 'once' conflicts with max_fires="
                          f"{mf} — once caps the rule at one fire "
                          f"cluster-wide")
        rid = d.get("id")
        if rid:
            if rid in seen_ids:
                issues.append(
                    f"{tag}: duplicate rule id {rid!r} (also rule "
                    f"#{seen_ids[rid]}) — `once` claims are keyed by "
                    f"id, so duplicates starve each other and at most "
                    f"one ever fires")
            else:
                seen_ids[rid] = i
    return issues


# ------------------------------------------------------------------ metric

def _ensure_counter():
    global _counter
    if _counter is None:
        from .. import metrics
        _counter = metrics.Counter(
            METRIC_NAME,
            "Faults injected by the chaos layer", ("site", "action"))
    return _counter


def _count(site: str, action: str) -> None:
    c = _ensure_counter()
    c.inc(tags={"site": site, "action": action})


def count_injection(site: str, action: str) -> None:
    """Record an injection observed REMOTELY (a crashing worker's
    last-gasp notify lands in its nodelet's registry — worker registries
    are never scraped, and the process is gone a millisecond later)."""
    _count(site, action)


def injected_counts() -> Dict[str, float]:
    """site|action -> count for this process (chaos status CLI)."""
    if _counter is None:
        return {}
    return {"|".join(k): v for k, v in _counter._samples()}


# ------------------------------------------------------------- once claims

def local_claim(rule_id: str) -> bool:
    """Per-process `once` fallback when no controller is reachable."""
    with _lock:
        if rule_id in _local_claims:
            return False
        _local_claims.add(rule_id)
        return True


def chaos_env(plan: List[Dict[str, Any]]) -> Dict[str, str]:
    """Env block that arms spawned processes with ``plan`` (the
    cluster_utils / add_node(env=...) plumbing)."""
    return {"RAY_TPU_CHAOS_PLAN": json.dumps(plan)}
