"""Serve configuration schemas (reference: `serve/config.py`)."""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional


@dataclasses.dataclass
class AutoscalingConfig:
    min_replicas: int = 1
    max_replicas: int = 4
    # capacity unit of a replica WITHOUT a decode engine: router-
    # reported in-flight requests per replica (the pre-engine signal,
    # still the fallback for plain deployments).  Engine replicas use
    # their real slot capacity instead.
    target_num_ongoing_requests_per_replica: float = 2.0
    # cooldowns between applied scale decisions, per direction —
    # hysteresis in time, so bursty traffic cannot flap the fleet
    upscale_delay_s: float = 0.0
    downscale_delay_s: float = 2.0
    # -- occupancy-trend policy (serve/autoscaler.py) ---------------------
    # utilization the fleet is sized toward after a scale decision
    target_occupancy: float = 0.6
    # scale up once recent utilization crosses this watermark (or any
    # sessions are waiting for slots) — BEFORE saturation sheds
    occupancy_high: float = 0.8
    # scale down only when utilization over the whole trend window
    # stays under this watermark; the [low, high] band is the
    # hysteresis dead zone where the fleet holds steady
    occupancy_low: float = 0.3
    # look-back the policy trends over (occupancy/waiting series from
    # `state.metrics_history` or the controller's own sample ring)
    trend_window_s: float = 10.0
    # capacity weight of a replica whose node is SUSPECT (gray
    # failure): counting it at full weight hides the brownout, zero
    # would thrash on every transient quarantine.  Down-weighted
    # replicas are also first in line as scale-down victims.
    suspect_weight: float = 0.25


@dataclasses.dataclass
class HTTPOptions:
    host: str = "127.0.0.1"
    port: int = 8000
    # "HeadOnly": one proxy in the driver's node (default).
    # "EveryNode": the controller reconciles one proxy actor per alive
    #   node, each binding an ephemeral port announced in the proxy
    #   table (reference: http_state.py per-node proxy management; fixed
    #   per-node ports are impossible here because test clusters share
    #   one host/IP).
    # "NoServer": handles only, no HTTP ingress.
    location: str = "HeadOnly"


@dataclasses.dataclass
class DecodeEngineConfig:
    """Knobs of the replica-resident continuous-batching decode engine
    (`serve/decode_session.py`).  One fixed-slot batched KV cache and
    one jitted decode step are shared by every live session; these
    bounds govern admission and token buffering."""
    # decode slots in the batched KV cache — the compiled batch size.
    # Sessions beyond this wait for a slot (iteration-level admission).
    max_slots: int = 8
    # per-session bounded token queue: the engine decodes ahead of the
    # client by at most this many tokens, then pauses the slot
    token_queue_depth: int = 64
    # sessions allowed to wait for a slot before `start` is rejected
    # with ReplicaUnavailableError (→ HTTP 503 + Retry-After)
    max_waiting: int = 32
    # how long a `next_chunk` drain will linger for its chunk to fill
    # once at least one token is buffered (amortizes transport without
    # stalling slow decodes)
    chunk_linger_s: float = 0.025
    # server-side cap on one `next_chunk` wait with an empty queue
    chunk_timeout_s: float = 30.0
    # leak reaper: a session whose client has not polled (`next_chunk`)
    # for this long is evicted and its slot reclaimed — abandoned
    # streams (client crashed without `end`) must not hold decode slots
    # or session-table memory forever.  <= 0 disables the reaper.
    session_idle_ttl_s: float = 120.0
    # -- chunked-prefill admission ----------------------------------------
    # a joining session's prompt is consumed [1, chunk] tokens at a time
    # BETWEEN shared decode steps on the engine thread (the remainder
    # as one more chunk, padded) — admission and failover resume reuse
    # the one compiled chunk shape, and a join never stalls live streams
    # by more than one chunk interval.  None (the default) derives the
    # width from the chip: the widest power of two under its ridge point
    # for the weights' item size (128 for bfloat16 on a v5e; 32 on a
    # backend with no published peaks), so the interval is about two
    # reads of the weights, about two small-batch decode steps
    # (`decode_session.prefill_chunk_width`); a test or a deployment
    # may pin it.  The engine's own `ecfg` holds the width in use.
    prefill_chunk_tokens: Optional[int] = None
    # bound on one `start`/`resume` call: enqueue -> first token (the
    # prompt is prefilled by the engine thread; a wedged engine must not
    # hang the caller forever — timeout sheds with the typed 503)
    admission_timeout_s: float = 60.0
    # -- shared-prefix KV reuse -------------------------------------------
    # admission consults a radix trie over live slots' prompts
    # (serve/prefix_cache.py): a new session sharing a prefix with a
    # resident slot copies those K/V rows (`models.cache_gather_slot`)
    # and chunk-prefills ONLY the unshared suffix — shared system
    # prompts skip their prefill entirely
    prefix_cache: bool = True
    # minimum shared tokens worth a gather dispatch (a 1-2 token match
    # costs more in dispatch than it saves in prefill)
    prefix_cache_min_tokens: int = 4

    def __post_init__(self):
        if self.token_queue_depth < 1:
            # a session admitted in a turn that dispatches no step never
            # gets its first token into the carry: the engine would hang
            raise ValueError(
                f"token_queue_depth must be at least 1, got "
                f"{self.token_queue_depth}: a session's queue has to hold "
                f"the token of the step that carries it")


@dataclasses.dataclass
class DeploymentConfig:
    num_replicas: int = 1
    max_concurrent_queries: int = 8
    user_config: Optional[Any] = None
    autoscaling_config: Optional[AutoscalingConfig] = None
    ray_actor_options: Dict[str, Any] = dataclasses.field(
        default_factory=dict)
    version: int = 0
    # -- gang replicas (serve/gang.py): one replica spanning N processes --
    gang_size: int = 1                    # >1 → replica is a mesh gang
    gang_mesh: Optional[str] = None       # MeshSpec text, e.g. "tp=2"
    gang_strategy: str = "PACK"           # placement group strategy
