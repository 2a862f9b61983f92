"""Serve fleet autoscaler + session-aware prefix-cache routing (PR-12).

Tier-1, CPU: pure-policy units (trend-up, hysteresis, cooldown, SUSPECT
down-weight, victim selection), prefix-trie units (insert /
longest-match / evict-on-slot-reclaim / hit accounting), engine-level
shared-prefix admission (byte parity + skipped prefill), controller
loop mechanics with fake replicas (scale-up, drain-down retirement,
chaos-dropped decision retried without double-scaling, boot-EWMA
Retry-After), router prefix affinity + draining skip, and the
per-deployment metrics-history filter."""

import time

import pytest

from greedy_reference import greedy_stream
from ray_tpu.serve import autoscaler
from ray_tpu.serve.autoscaler import FleetSample, ReplicaView
from ray_tpu.serve.prefix_cache import PrefixIndex


def _tiny_cfg(max_seq_len=64):
    import jax.numpy as jnp

    from ray_tpu.models import TransformerConfig
    return TransformerConfig.tiny(max_seq_len=max_seq_len,
                                  attention_impl="reference",
                                  dtype=jnp.float32)


def _views(n, occupied=0.0, waiting=0.0, capacity=8.0, suspect=()):
    return [ReplicaView(replica_id=f"d#{i}", occupied=occupied,
                        waiting=waiting, capacity=capacity,
                        suspect=(i in suspect)) for i in range(n)]


def _series(now, pts, waiting=0.0):
    """Evenly spaced samples ending at ``now`` (1s apart)."""
    n = len(pts)
    return [FleetSample(ts=now - (n - 1 - i), utilization=u,
                        waiting=waiting) for i, u in enumerate(pts)]


AUTO = {"min_replicas": 1, "max_replicas": 4,
        "occupancy_high": 0.8, "occupancy_low": 0.3,
        "target_occupancy": 0.6, "trend_window_s": 10.0,
        "upscale_delay_s": 0.0, "downscale_delay_s": 0.0,
        "suspect_weight": 0.25}


# ---------------------------------------------------------- policy units

def test_policy_trend_up_scales_up():
    now = 100.0
    views = _views(2, occupied=7.0, capacity=8.0)
    series = _series(now, [0.2, 0.4, 0.7, 0.9, 0.9])
    d = autoscaler.decide(AUTO, views, series, now)
    assert d.target > 2 and d.reason.startswith("up")


def test_policy_waiting_depth_scales_up_before_saturation():
    """Sessions queued for busy slots scale the fleet even when
    occupancy has not yet crossed the high watermark — scale-up lands
    BEFORE the admission-backpressure 503s start.  A waiting session
    while slots sit idle (admission latency, not load) does NOT."""
    now = 50.0
    busy = _views(2, occupied=6.0, waiting=3.0, capacity=8.0)
    series = _series(now, [0.75, 0.75, 0.75], waiting=3.0)
    d = autoscaler.decide(AUTO, busy, series, now)
    assert d.target > 2 and d.reason.startswith("up")
    idle = _views(2, occupied=1.0, waiting=1.0, capacity=8.0)
    series = _series(now, [0.12, 0.12, 0.12], waiting=1.0)
    d = autoscaler.decide(AUTO, idle, series, now)
    assert d.target == 2 and d.reason == ""


def test_policy_hysteresis_band_holds():
    now = 100.0
    views = _views(2, occupied=4.0, capacity=8.0)
    series = _series(now, [0.5] * 8)
    d = autoscaler.decide(AUTO, views, series, now)
    assert d.target == 2 and d.reason == ""


def test_policy_cooldown_blocks_consecutive_scale_ups():
    now = 100.0
    views = _views(2, occupied=7.5, capacity=8.0)
    series = _series(now, [0.9] * 6)
    auto = dict(AUTO, upscale_delay_s=5.0)
    held = autoscaler.decide(auto, views, series, now, last_up=now - 1.0)
    assert held.target == 2 and held.reason == ""
    again = autoscaler.decide(auto, views, series, now,
                              last_up=now - 6.0)
    assert again.target > 2


def test_policy_suspect_down_weight_triggers_scale_up():
    """8 in-flight over 2x8 slots is 50% — comfortable.  With one
    replica on a SUSPECT node its capacity counts at 0.25: the same
    load reads as a brownout and the fleet pre-emptively grows."""
    now = 100.0
    healthy = _views(2, occupied=4.0, capacity=8.0)
    series_h = [autoscaler.fleet_sample(now - i, healthy, 0.25)
                for i in (2, 1, 0)]
    assert autoscaler.decide(AUTO, healthy, series_h, now).reason == ""

    sus = _views(2, occupied=4.0, capacity=8.0, suspect=(1,))
    series_s = [autoscaler.fleet_sample(now - i, sus, 0.25)
                for i in (2, 1, 0)]
    d = autoscaler.decide(AUTO, sus, series_s, now)
    assert d.target > 2 and d.reason.startswith("up")


def test_policy_scale_down_picks_least_loaded_victim():
    now = 100.0
    views = [ReplicaView("d#0", occupied=5.0, capacity=8.0),
             ReplicaView("d#1", occupied=0.0, capacity=8.0),
             ReplicaView("d#2", occupied=1.0, capacity=8.0)]
    series = _series(now, [0.1] * 10)
    d = autoscaler.decide(AUTO, views, series, now)
    assert d.target < 3 and d.reason.startswith("down")
    assert d.victims[0] == "d#1"      # emptiest drains first


def test_policy_scale_down_prefers_suspect_victims():
    now = 100.0
    views = [ReplicaView("d#0", occupied=0.0, capacity=8.0),
             ReplicaView("d#1", occupied=2.0, capacity=8.0,
                         suspect=True)]
    series = _series(now, [0.05] * 10)
    d = autoscaler.decide(AUTO, views, series, now)
    assert d.reason.startswith("down") and d.victims[0] == "d#1"


def test_policy_never_scales_below_min_or_above_max():
    now = 100.0
    crazy_high = _series(now, [5.0] * 5, waiting=50.0)
    d = autoscaler.decide(AUTO, _views(4, occupied=8.0, waiting=20.0),
                          crazy_high, now)
    assert d.target == 4                      # clamped at max
    idle = _series(now, [0.0] * 10)
    d = autoscaler.decide(AUTO, _views(1), idle, now)
    assert d.target == 1 and d.reason == ""   # already at min


def test_policy_downscale_cooldown_and_empty_series_hold():
    now = 100.0
    views = _views(3)
    d = autoscaler.decide(AUTO, views, [], now)
    assert d.target == 3 and d.reason == ""   # no signal: hold
    idle = _series(now, [0.0] * 10)
    auto = dict(AUTO, downscale_delay_s=30.0)
    d = autoscaler.decide(auto, views, idle, now, last_down=now - 5.0)
    assert d.target == 3 and d.reason == ""


# ------------------------------------------------------ prefix-trie units

def test_trie_insert_longest_match_and_accounting():
    ix = PrefixIndex()
    ix.insert([1, 2, 3, 4, 5], "a")
    ix.insert([1, 2, 9], "b")
    owner, depth = ix.longest_match([1, 2, 3, 4, 7, 8])
    assert (owner, depth) == ("a", 4)
    owner, depth = ix.longest_match([1, 2, 9, 9])
    assert (owner, depth) == ("b", 3)
    assert ix.longest_match([7, 7]) == (None, 0)
    st = ix.stats()
    assert st["hits"] == 2 and st["misses"] == 1
    assert st["tokens_matched"] == 7 and st["entries"] == 2


def test_trie_cap_bounds_usable_depth():
    """An admission must recompute at least the prompt's last token, so
    lookups cap the match depth."""
    ix = PrefixIndex()
    ix.insert([5, 6, 7, 8], "a")
    owner, depth = ix.longest_match([5, 6, 7, 8], cap=3)
    assert (owner, depth) == ("a", 3)


def test_trie_evict_on_slot_reclaim():
    """Re-inserting an owner (slot reassigned to a new prompt) replaces
    its key, and evict() removes it outright — stale donors must never
    match."""
    ix = PrefixIndex()
    ix.insert([1, 2, 3, 4, 5, 6], 0)
    assert ix.longest_match([1, 2, 3, 4])[0] == 0
    ix.insert([9, 8, 7, 6], 0)        # slot 0 reclaimed by a new prompt
    assert ix.longest_match([1, 2, 3, 4]) == (None, 0)
    assert ix.longest_match([9, 8])[0] == 0
    assert ix.evict(0) is True
    assert ix.longest_match([9, 8]) == (None, 0)
    assert len(ix) == 0 and not ix._root.children  # branches pruned


def test_trie_max_owners_lru_bound():
    ix = PrefixIndex(max_owners=2)
    ix.insert([1, 1], "a")
    ix.insert([2, 2], "b")
    ix.insert([3, 3], "c")            # evicts the oldest ("a")
    assert ix.longest_match([1, 1]) == (None, 0)
    assert ix.longest_match([3, 3])[0] == "c"
    assert len(ix) == 2


# ----------------------------------------- models gather-slot + engine

def test_cache_gather_slot_roundtrip_and_truncation():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import (cache_gather_slot, cache_insert_slot,
                                init_kv_cache, init_params, init_slot_cache,
                                prefill)
    cfg = _tiny_cfg()
    params, _ = init_params(jax.random.PRNGKey(0), cfg)
    prompt = jnp.asarray([[3, 1, 4, 1, 5, 9, 2, 6]], jnp.int32)
    cache = init_kv_cache(cfg, 1, 64)
    _, cache = prefill(params, prompt, cfg, cache)
    slot_cache = init_slot_cache(cfg, 4, 64)
    slot_cache = cache_insert_slot(slot_cache, cache, jnp.int32(2))
    got = cache_gather_slot(slot_cache, jnp.int32(2), jnp.int32(5))
    assert int(got["pos"]) == 5
    # [layers, batch, kv_heads, head_dim, max_len]: positions last
    np.testing.assert_array_equal(np.asarray(got["k"][:, 0, ..., :5]),
                                  np.asarray(cache["k"][:, 0, ..., :5]))
    np.testing.assert_array_equal(np.asarray(got["v"][:, 0, ..., :5]),
                                  np.asarray(cache["v"][:, 0, ..., :5]))


def test_engine_prefix_reuse_parity_and_skipped_prefill():
    """Two sessions sharing a 12-token system prompt: the second admits
    through a donor-slot gather and prefills only its suffix — byte-
    identical streams to the greedy reference, one applied hit, and the
    shared tokens never re-run a prefill chunk."""
    from ray_tpu.serve.decode_session import DecodeSessionCore
    cfg = _tiny_cfg()
    core = DecodeSessionCore(cfg, max_len=64, seed=3)
    system = [7, 3, 9, 4, 8, 1, 6, 2, 5, 0, 7, 7]
    pa, pb = system + [11, 13], system + [17, 19, 23]

    def stream(c, p, n):
        r = c.handle({"op": "start", "prompt": p})
        toks = list(r["token"])
        while len(toks) < n:
            out = c.handle({"op": "next_chunk", "sid": r["sid"],
                            "max_tokens": n - len(toks)})
            toks += out["tokens"]
            if out.get("done"):
                break
        c.handle({"op": "end", "sid": r["sid"]})
        return toks[:n]

    a = stream(core, pa, 10)
    chunks_after_a = core.handle({"op": "stats"})["engine"][
        "prefill_chunks"]
    b = stream(core, pb, 10)
    st = core.handle({"op": "stats"})["engine"]
    assert a == greedy_stream(cfg, pa, 10, max_len=64, seed=3)
    assert b == greedy_stream(cfg, pb, 10, max_len=64, seed=3)
    assert st["prefix"]["applied_hits"] == 1, st["prefix"]
    assert st["prefix"]["tokens_reused"] == len(system)
    # B's admission burned ONE chunk program, padded, for its 3-token
    # suffix (A's 14 tokens were one too)
    assert chunks_after_a == 1
    assert st["prefill_chunks"] - chunks_after_a == 1
    assert st["prefill_pad_tokens"] == (32 - 14) + (32 - 3)
    from ray_tpu import metrics
    text = metrics.prometheus_text()
    assert "ray_tpu_serve_prefix_hits_total" in text
    assert "ray_tpu_serve_prefix_tokens_reused_total" in text


def test_engine_prefix_cache_disabled_stays_cold():
    from ray_tpu.serve.config import DecodeEngineConfig
    from ray_tpu.serve.decode_session import DecodeSessionCore
    cfg = _tiny_cfg()
    core = DecodeSessionCore(
        cfg, max_len=64, seed=3,
        engine=DecodeEngineConfig(prefix_cache=False))
    p = [5, 5, 5, 5, 5, 5, 1]
    for _ in range(2):
        r = core.handle({"op": "start", "prompt": p})
        core.handle({"op": "end", "sid": r["sid"]})
    st = core.handle({"op": "stats"})["engine"]
    assert st["prefix"]["applied_hits"] == 0
    assert st["prefix"]["entries"] == 0


def test_group_start_routes_batched_prompts_through_engine():
    """A batched start becomes per-row engine sessions behind a grp:
    sid whose replies carry one token a row, and the rows' streams match
    the greedy reference row for row."""
    from ray_tpu.serve.decode_session import DecodeSessionCore
    cfg = _tiny_cfg()
    core = DecodeSessionCore(cfg, max_len=64, seed=3)
    prompts = [[3, 1, 4, 1], [2, 7, 1, 8]]
    r = core.handle({"op": "start", "prompt": prompts})
    assert isinstance(r["sid"], str) and r["sid"].startswith("grp:")
    assert len(r["token"]) == 2
    got = [list(r["token"])]
    for _ in range(5):
        got.append(core.handle({"op": "next", "sid": r["sid"]})["token"])
    assert core.handle({"op": "end", "sid": r["sid"]})["ended"]
    rows = [greedy_stream(cfg, p, 6, max_len=64, seed=3) for p in prompts]
    assert got == [list(step) for step in zip(*rows)]
    assert core.handle({"op": "stats"})["groups"] == 0
    # unknown group after end
    out = core.handle({"op": "next", "sid": r["sid"]})
    assert "error" in out


# ------------------------------------------- controller loop (no cluster)

class _FakeDrainHandle:
    """Stands in for a replica actor handle in controller unit tests:
    remote() calls raise (the controller's try/except paths treat that
    as live_sessions == 0 / kill done), which is exactly the plain-
    replica behavior the retirement path must survive."""

    class _M:
        def remote(self, *a, **k):
            raise RuntimeError("no cluster in unit test")

    def __getattr__(self, item):
        if item.startswith("_"):
            raise AttributeError(item)
        return self._M()

    _actor_id = b"fake"


def _bare_controller(monkeypatch):
    import ray_tpu.state as state_mod
    from ray_tpu.serve.controller import ServeController
    ctl = ServeController.__new__(ServeController)
    ctl._deployments = {}
    ctl._version = 0
    ctl._replica_seq = 0
    ctl._proxies = {}
    ctl._proxy_http = None
    ctl._last_proxy_check = time.monotonic() + 3600
    ctl._replica_nodes = {}
    ctl._evacuations = {}
    ctl._retiring = {}
    ctl._suspect_nodes = set()
    ctl._boot_pending = {}
    ctl._boot_ewma = None
    ctl._last_autoscale = 0.0
    monkeypatch.setattr(state_mod, "report_event",
                        lambda *a, **k: None)
    monkeypatch.setattr(ServeController, "_engine_history",
                        staticmethod(lambda: {}))
    monkeypatch.setattr(ServeController, "_observe_boots",
                        lambda self, now: None)
    monkeypatch.setattr(ServeController, "_push_deployment_metrics",
                        lambda self: None)

    def fake_start(self, name, entry):
        self._replica_seq += 1
        rep = {"id": f"{name}#{self._replica_seq}",
               "handle": _FakeDrainHandle()}
        entry["replicas"].append(rep)
        return rep
    monkeypatch.setattr(ServeController, "_start_replica", fake_start)
    return ctl


def _seed_deployment(ctl, name="dep", replicas=1, **auto):
    entry = {"replicas": [], "metrics": {}, "last_scaled": 0.0,
             "config": {"num_replicas": replicas,
                        "autoscaling_config": dict(AUTO, **auto)}}
    ctl._deployments[name] = entry
    for _ in range(replicas):
        ctl._start_replica(name, entry)
    return entry


def _tick(ctl, entry, ongoing):
    """One forced autoscale pass with router-reported counts."""
    entry["metrics"] = {"ongoing": ongoing, "ts": time.monotonic()}
    ctl._last_autoscale = 0.0
    ctl._maybe_autoscale()


def test_controller_scales_up_then_retires_down(monkeypatch):
    ctl = _bare_controller(monkeypatch)
    entry = _seed_deployment(ctl, replicas=1,
                             target_num_ongoing_requests_per_replica=1.0,
                             downscale_delay_s=0.0)
    rid0 = entry["replicas"][0]["id"]
    # sustained load: 6 in flight on one replica -> scale up
    for _ in range(3):
        _tick(ctl, entry, {rid0: 6})
        time.sleep(0.01)
    assert len(entry["replicas"]) > 1
    assert entry["config"]["num_replicas"] == len(entry["replicas"])
    # idle long enough to drain the trend window -> victims retire
    # through the drain path (marked, then killed at live==0)
    deadline = time.monotonic() + 20
    while time.monotonic() < deadline:
        _tick(ctl, entry, {r["id"]: 0 for r in entry["replicas"]})
        if len(entry["replicas"]) == 1 and not ctl._retiring:
            break
        time.sleep(0.05)
    assert len(entry["replicas"]) == 1
    assert not ctl._retiring
    assert entry["config"]["num_replicas"] == 1


def test_controller_chaos_dropped_decision_retries_never_doubles(
        monkeypatch):
    """Satellite: chaos site serve.autoscale drops the FIRST decision;
    the next tick re-derives it from current state.  Targets are
    absolute, so the retried decision lands exactly once — replica
    count goes to the policy target, not target + N."""
    from ray_tpu.util import fault_injection as fi
    ctl = _bare_controller(monkeypatch)
    entry = _seed_deployment(ctl, replicas=1,
                             target_num_ongoing_requests_per_replica=1.0)
    rid0 = entry["replicas"][0]["id"]
    fi.arm([{"site": "serve.autoscale", "action": "drop",
             "match": {"nth": 1}}])
    try:
        _tick(ctl, entry, {rid0: 6})
        assert len(entry["replicas"]) == 1      # decision dropped
        _tick(ctl, entry, {rid0: 6})
        first = len(entry["replicas"])
        assert first > 1                        # retried and applied
        _tick(ctl, entry, {r["id"]: 6 // first
                           for r in entry["replicas"]})
        assert len(entry["replicas"]) == first  # no double-scale
    finally:
        fi.disarm()


def test_controller_suspect_node_down_weights_capacity(monkeypatch):
    ctl = _bare_controller(monkeypatch)
    entry = _seed_deployment(ctl, replicas=2,
                             target_num_ongoing_requests_per_replica=4.0)
    r0, r1 = [r["id"] for r in entry["replicas"]]
    ctl._replica_nodes[r1] = "nodeB"
    load = {r0: 2, r1: 2}      # 50% of 2x4: comfortable when healthy
    for _ in range(3):
        _tick(ctl, entry, dict(load))
    assert len(entry["replicas"]) == 2
    ctl._suspect_nodes.add("nodeB")             # gray node
    for _ in range(3):
        _tick(ctl, entry, dict(load))
    assert len(entry["replicas"]) > 2


def test_boot_ewma_retry_after_hint():
    from ray_tpu.serve.controller import ServeController
    ctl = ServeController.__new__(ServeController)
    now = time.monotonic()
    ctl._boot_ewma = 6.0
    ctl._boot_pending = {"dep#7": now - 2.0, "other#1": now - 5.0}
    hint = ctl._scaleup_retry_after("dep", now)
    assert hint == pytest.approx(4.0, abs=0.2)
    # late in the boot the hint floors instead of going negative
    ctl._boot_pending["dep#7"] = now - 50.0
    assert ctl._scaleup_retry_after("dep", now) == 0.5
    # no scale-up in flight -> no hint (generic floor applies)
    assert ctl._scaleup_retry_after("nope", now) is None
    ctl._boot_ewma = None
    assert ctl._scaleup_retry_after("dep", now) is None


# ----------------------------------------------------- router-level units

def _bare_router(table):
    import itertools
    import threading

    from ray_tpu.serve.prefix_cache import PrefixIndex
    from ray_tpu.serve.router import Router
    r = Router.__new__(Router)
    r._controller = None
    r._version = 0
    r._table = table
    r._inflight = {}
    r._rr = {name: itertools.cycle(range(max(len(e["replicas"]), 1)))
             for name, e in table.items()}
    r._lock = threading.Lock()
    r._poll_interval = 1e9
    r._last_poll = time.monotonic() + 1e9   # _refresh never fires
    r._node_id = None
    r._down_nodes = set()
    r._paffinity = PrefixIndex(max_owners=64)
    r._paff_owner = {}
    r._paff_seq = 0
    r._refresh = lambda force=False: None   # no controller in units
    return r


class _FakeReplicaHandle:
    class _Req:
        def remote(self, *a, **k):
            return "ref"

    handle_request = _Req()


def _table(*rids, draining=(), cap=8, retry_after=None):
    return {"dep": {
        "route_prefix": "/dep", "ingress": False,
        "max_concurrent_queries": cap,
        "scaleup_retry_after_s": retry_after,
        "replicas": [{"id": rid, "handle": _FakeReplicaHandle(),
                      "node_id": None,
                      "draining": rid in draining}
                     for rid in rids]}}


def test_router_prefix_affinity_sticks_sessions_together():
    router = _bare_router(_table("r1", "r2"))
    system = list(range(20))
    _, first = router.assign_request("dep", (), {},
                                     prefix_tokens=system + [99])
    router.complete = lambda *a: None   # no controller in unit test
    for i in range(4):
        _, rid = router.assign_request("dep", (), {},
                                       prefix_tokens=system + [i])
        assert rid == first    # RR alone would alternate replicas
        with router._lock:
            router._inflight[rid] -= 1


def test_router_prefix_affinity_yields_to_load():
    router = _bare_router(_table("r1", "r2"))
    system = list(range(20))
    _, first = router.assign_request("dep", (), {},
                                     prefix_tokens=system)
    other = "r2" if first == "r1" else "r1"
    with router._lock:
        router._inflight[first] = 5    # hot replica way above sibling
    _, rid = router.assign_request("dep", (), {},
                                   prefix_tokens=system + [1])
    assert rid == other


def test_router_skips_draining_replicas_for_new_sessions():
    router = _bare_router(_table("r1", "r2", draining=("r1",)))
    for _ in range(4):
        _, rid = router.assign_request("dep", (), {})
        assert rid == "r2"
        with router._lock:
            router._inflight[rid] -= 1
    # sticky ops still reach the draining owner (migrating handoff)
    _, rid = router.assign_request("dep", (), {},
                                   sticky_replica_id="r1")
    assert rid == "r1"


def test_router_shed_carries_scaleup_retry_after():
    from ray_tpu.exceptions import ReplicaUnavailableError
    router = _bare_router(_table(retry_after=7.5))
    with pytest.raises(ReplicaUnavailableError) as ei:
        router.assign_request("dep", (), {}, timeout_s=0.5)
    assert ei.value.retry_after_s == 7.5


# -------------------------------------- metrics-history deployment filter

def test_metrics_history_series_deployment_filter():
    from ray_tpu.core import metrics_history as mh
    samples = [{
        "ts": 10.0,
        "counters": {},
        "gauges": {
            'ray_tpu_serve_engine_occupied_slots{deployment="a",'
            'replica="a#1"}': 3.0,
            'ray_tpu_serve_engine_occupied_slots{deployment="b",'
            'replica="b#1"}': 7.0,
        }}]
    got = mh.series(samples, "ray_tpu_serve_engine_occupied_slots",
                    kind="gauges", labels={"deployment": "a"})
    assert len(got) == 1 and got[0]["value"] == 3.0
    assert mh.parse_labels(got[0]["key"])["replica"] == "a#1"
    both = mh.series(samples, "ray_tpu_serve_engine_occupied_slots",
                     kind="gauges")
    assert len(both) == 2


def test_chaos_validate_knows_serve_autoscale_site():
    from ray_tpu.util.fault_injection import validate_plan
    issues = validate_plan([{"site": "serve.autoscale",
                             "action": "drop", "match": {"nth": 1}}])
    assert not issues
    issues = validate_plan([{"site": "serve.autoscale",
                             "action": "kill_worker"}])
    assert issues


def test_nodelet_folds_prefix_counter_deltas():
    """PR-14 (found by the rpc-payload-contract rule): engines push
    prefix-cache counters CUMULATIVELY in `serve_metrics`; the nodelet
    must fold positive deltas into its own registry (worker registries
    are never scraped) and treat a shrink as an engine restart."""
    import asyncio

    import ray_tpu.metrics as metrics
    from ray_tpu.core import runtime_metrics as rtm
    from ray_tpu.core.nodelet import Nodelet

    def counter_value():
        for line in metrics.prometheus_text().splitlines():
            if line.startswith("ray_tpu_serve_prefix_hits_total") \
                    and 'deployment="fold_dep"' in line:
                return float(line.rsplit(" ", 1)[1])
        return 0.0

    n = object.__new__(Nodelet)
    n._serve_counter_seen = {}
    base = counter_value()

    async def push(hits):
        await Nodelet._h_serve_metrics(n, None, {
            "deployment": "fold_dep", "replica": "r0",
            "occupied": 1, "waiting": 0, "max_slots": 8,
            "prefix_hits": hits, "prefix_tokens_reused": 0})

    loop = asyncio.new_event_loop()
    try:
        loop.run_until_complete(push(3))     # first sample: +3
        assert counter_value() == base + 3
        loop.run_until_complete(push(5))     # cumulative 5: +2
        assert counter_value() == base + 5
        loop.run_until_complete(push(5))     # no growth: +0
        assert counter_value() == base + 5
        loop.run_until_complete(push(2))     # shrank: restart, +2
        assert counter_value() == base + 7
    finally:
        loop.close()
