"""Stateful KV-cache decode sessions for Serve replicas.

The serving-side face of the model runtime (reference: Ray Serve
delegates streaming decode to external engines like vLLM —
/root/reference/doc/source/serve/index.md; here it is in-tree): a
replica holds per-session KV caches so `start` pays one prefill and
every later token is one slot of a shared decode step.  Used by the
streaming-decode examples, `chip_smoke.py` and the benchmark's serve
cells; wrap a :class:`DecodeSessionCore` in a `@serve.deployment` whose
``__call__`` forwards to :meth:`DecodeSessionCore.handle`.

ONE decode data plane lives here, and one protocol with string sids
over it (:class:`DecodeSessionCore`): the **continuous-batching
engine**, a fixed-slot batched KV cache (`models.init_slot_cache`) and
ONE jitted batched decode step shared by every live session.  A
background loop decodes all active slots each iteration; sessions join
and vacate BETWEEN steps (iteration-level admission — vLLM's scheduling
insight), never recompiling: the batch shape is pinned at ``max_slots``
and the slot index of admission is a traced argument.  Decoded tokens
land in per-session bounded queues that the proxy drains via
``next_chunk`` (N tokens per RPC round trip), so neither a batch-1
decode step nor an RPC per token is on the path.  A replica therefore
has exactly one thing to route, autoscale and journal, and never
compiles a whole-prompt prefill program: the whole-prompt reference
(`models.generate`) is the tests' oracle, and shares no code with the
programs below.

The loop keeps ONE fused step in flight beyond the one whose tokens it
has read: step n+1 is dispatched from the device-resident carry (the
step's output feeds the next step, sampling is the argmax inside the
program, every live slot's position advances by one and which slots are
live is the host's own knowledge), THEN step n is read and published.
The chip works while the host reads, publishes and schedules; only token
VALUES lag the host by a step.

**Chunked-prefill admission** runs inside the same loop: a joining
session's prompt is consumed ``chunk`` tokens at a time between shared
decode steps (``DecodeEngineConfig.prefill_chunk_tokens``; unset, the
widest power of two under the chip's ridge point:
:func:`prefill_chunk_width`), so a join stalls live streams by at most
one chunk interval, about two reads of the weights and so about two
small-batch decode steps, instead of a whole prompt forward, and
TTFT-under-load stops being O(prompt_len) of batch stall.  A prompt's remainder is ONE more
program of the same width, padded, the count of its real tokens a
traced argument (`models.generate.prefill_chunk_step`).  Admission
and failover resume (``op: resume``) dispatch the SAME module-level
chunk program (`models.prefill_chunk_jit`, ``[1, chunk]``), and no
prompt length compiles anything.  ONE program a loop turn, however
many sessions join: while two or more prompts prefill, up to
:func:`prefill_lane_count` of them (arrival order) advance in one
program of ``[lanes, chunk]`` over a lane cache
(`models.prefill_lanes_jit`), which reads every weight once for the
lot; the others wait with no cache at all.  Two compiled prefill
shapes per model, whatever the traffic, both run once by the engine
itself before it serves its first session (`_warm_lanes`).
"""

from __future__ import annotations

import atexit
import collections
import dataclasses
import functools
import threading
import time
import weakref
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

from .config import DecodeEngineConfig

#: live engines, drained at interpreter exit — a daemon thread still
#: dispatching jitted steps while CPython tears down segfaults the
#: process (observed on this image), so every loop must be stopped and
#: joined BEFORE the runtime goes away
_ENGINES: "weakref.WeakSet" = weakref.WeakSet()


@atexit.register
def _shutdown_engines() -> None:
    for eng in list(_ENGINES):
        try:
            eng.shutdown()
        except Exception:
            pass


def _weights_ridge(params: Any, device_kind: Optional[str]
                   ) -> Optional[float]:
    """The chip's ridge point in rows for weights of ``params``' mean item
    size (`util.device_profile.ridge_rows`: 240.5 for bfloat16 on a v5e);
    None on a backend with no published peaks."""
    import jax

    from ..util.device_profile import ridge_rows
    leaves = jax.tree_util.tree_leaves(params)
    n = sum(int(a.size) for a in leaves)
    itemsize = sum(int(a.size) * a.dtype.itemsize
                   for a in leaves) / max(1, n)
    return ridge_rows(itemsize, device_kind)


def prefill_chunk_width(pinned: Optional[int], params: Any,
                        capacity: int,
                        device_kind: Optional[str] = None) -> int:
    """The width of the ONE chunk program: ``pinned`` where a caller set
    ``DecodeEngineConfig.prefill_chunk_tokens``, else the largest power
    of two not above the chip's ridge point for weights of ``params``'
    item size (`_weights_ridge`: 240.5 rows for bfloat16 on a v5e, so
    128), else, on a backend with no published peaks, 32; never more than
    ``capacity``.

    Under the ridge a program's matmuls are reads of the weights they
    touch, so prefill tokens a second grow with the width while a
    program, and with it the stall of the live streams, stays within
    about two reads of the weights: about two small-batch decode steps
    (measured on a v5e at 128 rows: 2.2 steps of the latent + routed
    model, PERF.md, PR 31).  Past it time grows with the rows.
    ``device_kind`` is the attached device's unless a test names one."""
    import math
    if pinned is not None:
        width = int(pinned)
    else:
        ridge = _weights_ridge(params, device_kind)
        width = 2 ** math.floor(math.log2(ridge)) if ridge else 32
    return min(max(1, width), capacity)


#: lanes of the chunk program on a backend with no published peaks
_LANES_UNKNOWN_CHIP = 4


def prefill_lane_count(chunk: int, params: Any,
                       device_kind: Optional[str] = None) -> int:
    """How many joining sessions ONE chunk program advances
    (`models.generate.prefill_lanes`), from what `prefill_chunk_width`
    reads: the power of two nearest to TWICE the ridge over the chunk (4
    lanes of 128 rows for bfloat16 on a v5e, 512 rows), no more than 8.

    Stacked rows read the weights once for the lot, so under the ridge a
    further lane is nearly free; past it the dense matmuls cost what they
    cost apart and only the routed experts (whose rows spread over many
    experts) still gain, while a lane that stands pays its rows of them.
    Measured on a v5e at 128 rows (PERF.md, PR 41): 2, 4 and 8 lanes."""
    import math
    ridge = _weights_ridge(params, device_kind)
    if not ridge:
        return _LANES_UNKNOWN_CHIP
    return min(8, max(2, 2 ** round(math.log2(2.0 * ridge / chunk))))


class _EngineSession:
    """One live session inside the engine, through three phases:
    *prefilling* (the engine thread consumes its prompt one fixed-shape
    chunk at a time, between decode steps: alone on a batch-1 cache of
    its own, in a LANE of the engine's lane cache while others prefill
    beside it, or queued for a lane with no cache at all), *waiting* (prompt
    fully prefilled into a batch-1 cache, first token produced, queued
    for a free slot), and *decoding* (cache inserted into its slot of
    the shared batched cache)."""

    __slots__ = ("sid", "slot", "queue", "first_tok", "last_tok", "pos",
                 "unread", "done", "error", "ended", "seq", "last_poll",
                 "prompt", "poff", "pcache", "plogits",
                 "ready", "shed", "ptoks", "rid", "t_enq", "t_pf",
                 "t_ready", "cond", "want", "lane")

    def __init__(self, sid: str, prompt: Any, lock: Any,
                 seq_base: int = 0, rid: str = ""):
        self.sid = sid
        # what THIS session's `next_chunk` callers wait on, over the
        # engine's one lock: a step's publish wakes a session's own
        # caller and only when it has something to do (`wake`), not
        # every caller of the replica for every token of every session
        self.cond = threading.Condition(lock)
        self.want = 0     # tokens the caller waiting in `next_chunk` asked for
        # ---- per-request phase marks (monotonic clock) ----
        self.rid = rid                # proxy-minted request id ("" = none)
        self.t_enq = time.monotonic()  # enqueued for chunked admission
        self.t_pf: Optional[float] = None     # first prefill chunk ran
        self.t_ready: Optional[float] = None  # first token produced
        # host copy of the prompt tokens: the shared-prefix index key
        # (inserted when this session takes a slot, matched by later
        # admissions)
        self.ptoks: tuple = ()
        self.slot: Optional[int] = None
        self.queue: collections.deque = collections.deque()
        # the first token, set when prefill completes: what `start`
        # replies with.  ``last_tok`` is the newest one, which a decode
        # step may already have moved on by the time the caller wakes
        self.first_tok: Optional[int] = None
        self.last_tok: Optional[int] = None
        # host mirror of cache pos, as of the last step DISPATCHED; of
        # the tokens dispatched, those the host has not read yet
        self.pos = 0
        self.unread = 0
        self.done = False             # no more tokens will be produced
        self.error: Optional[str] = None
        self.ended = False            # client sent `end`
        # seq of the next token to be DELIVERED (the start/resume reply
        # itself carries token seq_base) — replies stamp their first
        # token's seq so the failover client can dedupe replayed tokens
        # and detect a destructively-popped chunk whose reply was lost
        self.seq = seq_base + 1
        self.last_poll = time.monotonic()  # leak-reaper clock
        # ---- chunked-admission state (cleared once decoding) ----
        self.prompt = prompt          # [1, S] int32 on the HOST (numpy)
        self.poff = 0                 # tokens consumed so far
        self.pcache: Any = None       # target batch-1 cache being built
        # ... or its row of the engine's lane cache, while two or more
        # sessions prefill (`ContinuousBatchingEngine._lanes_advance`)
        self.lane: Optional[int] = None
        self.plogits: Any = None      # last chunk's final-position logits
        self.ready = False            # first token produced; start() may return
        self.shed = False             # drained mid-admission: typed 503

    def wake(self, was_empty: bool = False) -> None:
        """Under the engine's lock, after a change to what a caller in
        `next_chunk` waits for: the session's end, or new tokens.  Those
        wake it when they are the first it can take (its linger starts)
        or fill what it asked for; between the two it sleeps out its
        linger on its own clock."""
        if was_empty or self.done or len(self.queue) >= self.want:
            self.cond.notify_all()


class _Step(NamedTuple):
    """A fused step dispatched and not read yet."""
    batch: List[Tuple[_EngineSession, int]]   # its live sessions, each
    #                                           with the slot it held THEN
    out: Any          # [slots (+3)] int32 on the device: tokens (+ routing)
    rows: Tuple[int, ...]     # `CacheTraffic.step` of its batch
    # when the chip started on it, where the host can tell: a
    # ``perf_counter`` reading (nothing was queued before it), `_BEHIND`
    # (queued right behind the step before it: when that one's read
    # returns, if that read has to wait), None (other programs between)
    start: Optional[float]


_BEHIND = -1.0


class _LoopLock:
    """The engine's lock as its LOOP thread takes it: without blocking
    where nobody holds it (one `acquire(False)`), else a wait that is
    counted: a host annotation ``wait:lock`` in a profiler trace, seconds
    in ``phase_s["lock_wait"]`` (inside whichever ``engine:`` phase is
    open) and one in ``waits["lock_waits"]``.  The callers' threads take
    ``eng._cond`` as ever: what THEY wait is not counted."""

    __slots__ = ("eng",)

    def __init__(self, eng: "ContinuousBatchingEngine"):
        self.eng = eng

    def __enter__(self) -> None:
        eng = self.eng
        if eng._lock.acquire(False):
            return
        from ..util import tracing
        with tracing.span("wait:lock", into=(eng.phase_s, "lock_wait")):
            eng._lock.acquire()
        eng.waits["lock_waits"] += 1

    def __exit__(self, *exc) -> None:
        self.eng._lock.release()


class ContinuousBatchingEngine:
    """Replica-resident continuous-batching decode loop.

    All slot-cache mutation happens on the engine thread, between
    steps — callers only enqueue admissions and drain token queues
    under the engine condition variable, so no device array is ever
    raced."""

    #: the engine thread's phases: `engine:<phase>` span of `_loop` (in
    #: a profiler trace, on the device lines' clock) -> the key its
    #: seconds are summed under in `phase_totals`
    _THREAD_PHASES = {"schedule": "schedule", "admit": "admit_host",
                      "dispatch": "dispatch", "readback": "readback",
                      "publish": "publish"}
    #: a step's read that takes this long is a stall, not a turn (at least
    #: twice the longest ordinary turn of any cell: a lanes program of
    #: 49 ms and a step of 16-20; PERF.md section 6, PR 51)
    _LONG_READ_S = 0.25

    def __init__(self, cfg, max_len: int, params: Any,
                 engine_cfg: DecodeEngineConfig, name: str = "",
                 replica_tag: str = "local"):
        import jax
        import jax.numpy as jnp

        from ..models import (CacheTraffic, cache_gather_slot,
                              cache_insert_slot, chunk_room,
                              prefill_chunk_jit, prefill_lanes_jit)
        from ..models.generate import (_decode_step_slots, cache_arrays,
                                       cache_bytes, greedy_tokens)
        self._cache_arrays, self._cache_bytes = cache_arrays, cache_bytes
        # a token is drawn from the next token's logits: head 0's, of a
        # model with several prediction heads
        self._greedy = functools.partial(greedy_tokens, cfg=cfg)
        self.cfg = cfg
        self.max_len = max_len
        self.params = params
        self.name = name or "decode"
        self._tag = replica_tag

        # a model whose expert layers drop nothing reports what a step
        # routed: (experts touched, largest expert load, pairs that
        # landed on an expert held here[, pairs that chose an identity
        # expert]), each summed over the layers that ROUTE (the
        # configuration says how many and how many sums: the engine
        # knows no router), ride BEHIND the tokens in the step's one
        # int32 vector, so the loop still makes one read per step
        self._moe_layers = moe_layers = cfg.expert_layers \
            if cfg.reports_load else 0
        slots = engine_cfg.max_slots

        def fused_step(params, tok, cache, active, *, cfg):
            # decode + greedy sample + carry in ONE program: the loop
            # pays a single dispatch and a single [S]-int32 device→host
            # read per step (separate argmax/where calls measurably
            # dominated the step on small models)
            if moe_layers:
                tok = tok[:slots]     # the last step's counts ride behind
            logits, cache, load = _decode_step_slots(params, tok, cache,
                                                     active, cfg)
            with jax.named_scope("head"):
                nxt = greedy_tokens(logits, cfg).astype(jnp.int32)
                out = jnp.where(active, nxt, tok)
                if moe_layers:
                    out = jnp.concatenate([out, jnp.stack(load)])
            return out, cache

        # ---- dispatch profiler (util/device_profile.py) ----
        # every jitted program below goes through a wrap-once timing
        # shim: dispatch counts, sampled device time, and the compile
        # ledger (first-seen argument shapes, and each compiled
        # program's op map for the traces) per program.  Snapshots
        # ride _maybe_push_metrics to the nodelet fold.
        from ..util.device_profile import DispatchProfiler
        self._prof = DispatchProfiler()
        # the slot cache is DONATED to the step and to the insert (as
        # the batch-1 cache is to the shared chunk program): each
        # extends it in place, and the engine thread, its only owner,
        # rebinds to the result
        self.cache_copies = 0
        self._step = self._prof.wrap(
            "decode_step", self._counting_copies(
                jax.jit(fused_step, static_argnames=("cfg",),
                        donate_argnames=("cache",)), 2))
        self._insert = self._prof.wrap(
            "cache_insert", self._counting_copies(
                jax.jit(cache_insert_slot, donate_argnums=(0,)), 0))
        # a joining slot's first token into the device-resident carry:
        # ``firsts`` is -1 wherever the carry stays (a token id is never
        # negative).  The carry is not donated: it may be the output of
        # the step in flight, which the host has yet to read.
        self._join = jax.jit(
            lambda carry, firsts: jnp.where(firsts >= 0, firsts, carry))
        # ---- shared-prefix KV reuse ----
        # radix trie over live slots' prompts (serve/prefix_cache.py):
        # admission copies the longest shared prefix out of a donor
        # slot and prefills only the unshared suffix.  Engine-thread
        # only, like the slot cache itself.
        self._prefix = None
        if getattr(engine_cfg, "prefix_cache", True):
            from .prefix_cache import PrefixIndex
            self._prefix = PrefixIndex()
        # one row of a slot-batched cache as a batch-1 cache: a donor's
        # prefix out of the slot cache, a finished prompt out of its lane
        self._gather = self._prof.wrap("prefix_gather",
                                       jax.jit(cache_gather_slot))
        self.prefix_hits = 0          # admissions seeded from a donor
        self.prefix_tokens_reused = 0  # prefill tokens skipped
        self._last_metrics_push = 0.0
        # the chunk program is the MODULE-LEVEL shared jit: admission
        # and failover resume here and `models.prefill_chunked` all hit
        # one compile cache.  The profiler wrap is idempotent, so an
        # engine restart re-wrapping the same shared jit never stacks a
        # second timer over it.
        self._chunk = self._prof.wrap(
            "prefill_chunk", self._counting_copies(prefill_chunk_jit, 2))
        # ... and the chunk program of SEVERAL sessions, under the same
        # name in the profiler, the compile ledger and a trace
        self._chunk_lanes = self._prof.wrap(
            "prefill_chunk", self._counting_copies(prefill_lanes_jit, 2))
        # the positions a chunk program's window may cover and the widest
        # chunk the cache's state kinds leave room for; the ONE chunk width
        # follows, and `ecfg` holds it resolved
        self._capacity, room = chunk_room(cfg, max_len)
        self.ecfg = dataclasses.replace(
            engine_cfg, prefill_chunk_tokens=prefill_chunk_width(
                engine_cfg.prefill_chunk_tokens, params, room))
        # ---- lanes: ONE chunk program for up to `_n_lanes` joining
        # sessions.  The lane cache (a slot cache of that many rows) is
        # held only while two or more sessions prefill; `_lane_sess[i]`
        # is who holds lane i.
        self._n_lanes = prefill_lane_count(
            self.ecfg.prefill_chunk_tokens, params)
        self._pool: Any = None
        self._lane_sess: List[Optional[_EngineSession]] = \
            [None] * self._n_lanes
        self._cache = None            # allocated lazily on first start
        self._shapes: set = set()     # distinct compiled program shapes
        # ONE lock.  `_cond` is what the engine thread and the callers in
        # `start` wait on; a caller in `next_chunk` waits on its
        # session's own condition over the same lock
        self._lock = threading.RLock()
        self._cond = threading.Condition(self._lock)
        self._loop_lock = _LoopLock(self)   # how the LOOP thread takes it
        self.sessions: Dict[str, _EngineSession] = {}  # insertion = LRU
        self._pending: List[_EngineSession] = []   # prefilled, want slot
        self._prefilling: List[_EngineSession] = []
        self._free: List[int] = list(range(engine_cfg.max_slots))
        self._slots: Dict[int, _EngineSession] = {}
        self._next_sid = 0
        self._thread: Optional[threading.Thread] = None
        self._shutdown = False
        self._draining = False   # replica evacuating: hand sessions off
        self.steps = 0
        self.tokens = 0
        self.reaped = 0          # sessions evicted by the idle reaper
        # chunks of prompts consumed (one a session a program), and the
        # chunk programs that consumed them: as many while prompts prefill
        # one at a time, up to `_n_lanes` chunks a program while several
        # do.  One ring span `engine:lanes` every `_MOE_SPAN_S` seconds
        # carries both since the last.
        self.prefill_chunks = 0
        self.prefill_programs = 0
        # ... and the cache rows those programs' attention moved from
        # memory, beside the rows a real query of theirs saw
        # (`CacheTraffic.chunk`)
        self.chunk_rows_fetched = self.chunk_rows_read = 0
        # ... the rows those programs fed (lanes x chunk), and the rows the
        # model's stateless tail ran of them (`CacheTraffic.tail_rows`)
        self.rows_fed = self.tail_rows = 0
        self._lanes_span = dict(self._lane_sums(), t=time.time())
        # ... of them those with fewer real tokens than the chunk holds
        # (a prompt's remainder), and the padding rows those carried
        self.prefill_tails = 0
        self.prefill_pad_tokens = 0
        # what the no-drop expert layers routed in decode steps, summed
        # over steps and expert layers: `stats()["moe"]`, and every
        # `_MOE_SPAN_S` seconds one ring span `moe:load` with the sums
        # since the last (a span a step cost too much: PERF.md, PR 25)
        self.moe = dict.fromkeys(
            ("steps", "experts_touched", "pairs", "load_max")
            + (("zero_pairs", "chosen") if cfg.zero_experts else ()), 0)
        self._moe_span = dict(self.moe, t=time.time())
        # what the decode steps read, moved and wrote of the cache, by state
        # kind (`models.CacheTraffic.STEP_SUMS`, which says what each sum
        # is): `stats()["cache"]` and, as `moe:load`, one ring span
        # `cache:rows` every `_MOE_SPAN_S` seconds with the sums since the
        # last
        self._traffic: Any = None     # the cache's, built beside it
        self.rows = dict.fromkeys(("steps",) + CacheTraffic.STEP_SUMS, 0)
        self._rows_span = dict(self.rows, t=time.time())
        # fused steps dispatched, and of them those dispatched while the
        # step before them had not been read: `stats()["steps_ahead"]`
        # and one ring span `engine:ahead` every `_MOE_SPAN_S` seconds
        self.ahead = dict.fromkeys(("steps", "steps_ahead"), 0)
        self._ahead_span = dict(self.ahead, t=time.time())
        # ---- the engine thread's own, between iterations ----
        self._carry: Any = None       # the last step's output, on the device
        self._flight: Optional[_Step] = None   # dispatched, not read
        self._active_dev: Any = None  # the live-slot mask on the device
        self._active_key = b""        # ... and what it was made from
        # when the newest read returned, and whether it had to wait for
        # the chip (then that is when its step ended)
        self._read_end, self._read_waited = 0.0, False
        # slot -> the session whose prompt the prefix index advertises
        # there (its `pos` is how far that slot's rings have moved on)
        self._donors: Dict[int, _EngineSession] = {}
        # analytic FLOPs/token per program -> the profiler's MFU
        # numerators (models.engine_flops_table; pure-copy programs 0)
        from ..models import engine_flops_table
        for prog, f in engine_flops_table(cfg, max_len).items():
            self._prof.set_flops_per_token(prog, f)
        # engine-side phase accumulators of the serve_breakdown table
        # (queue: enqueue -> first prefill chunk; admission: first
        # token -> decode slot); prefill/decode_dispatch walls come
        # from the profiler at snapshot time
        # cumulative seconds: per-session marks (queue, admission,
        # first_token), the engine thread's own phases (the `engine:`
        # spans of `_loop`), and the chunk programs that carried a
        # prompt's remainder (padded)
        # ... and what the engine thread waited for: its CPU seconds of
        # `schedule`, its waits for the engine's lock (`_LoopLock`), the
        # step reads that stalled (`_read`)
        self.phase_s = dict.fromkeys(
            ("queue", "admission", "first_token", "prefill_tail",
             "schedule_cpu", "lock_wait", "long_read")
            + tuple(self._THREAD_PHASES.values()), 0.0)
        self.waits = {"lock_waits": 0, "long_reads": 0}

    def _counting_copies(self, fn, arg: int):
        """``fn`` donates its positional argument ``arg``, a cache: count
        in ``cache_copies`` the calls after which that cache is still
        alive.  Donation that does not engage is silent otherwise (JAX
        warns once, and copies)."""

        @functools.wraps(fn)
        def call(*args, **kwargs):
            out = fn(*args, **kwargs)
            if not all(a.is_deleted()
                       for a in self._cache_arrays(args[arg]).values()):
                with self._cond:   # stats() reads this counter
                    self.cache_copies += 1
            return out

        return call

    # ------------------------------------------------------------ client ops

    def start(self, prompt, max_sessions: int, seq_base: int = 0,
              teacher_forced: bool = False,
              ptoks: Optional[tuple] = None,
              rid: str = "") -> Dict[str, Any]:
        """Enqueue one batch-1 prompt for chunked admission and block
        until the ENGINE THREAD has prefilled it — blocks of ``chunk``
        tokens (the remainder one more of them, padded), alone in a
        `[1, chunk]` program or beside other joining sessions' in a
        `[lanes, chunk]` one (`_admit_and_prefill`), interleaved between
        shared decode steps, so a joining session never stalls live
        streams by more than one chunk interval and admission reuses
        the compiled chunk shape of failover resume.  ``prompt`` is
        [1, S] token ids, taken to the HOST: the engine fills each
        chunk's buffer from it (a slice of a device array would be a
        small program of its own per length).  Returns the sid and
        first token; the session's remaining tokens start flowing once
        a slot frees (iteration-level admission).

        ``teacher_forced`` marks the failover-resume path: ``prompt``
        is a full replay prefix (original prompt + every token already
        delivered) and the session's token seqs continue from
        ``seq_base`` so the client can splice the resumed stream in
        without duplicates or gaps.  Resume IS admission here — both
        walk the same chunk programs, so resumes never compile-storm."""
        import numpy as np

        from ..exceptions import ReplicaUnavailableError
        s_len = int(prompt.shape[1])
        if s_len > self._capacity:
            raise ValueError(f"prompt length {s_len} exceeds cache "
                             f"capacity {self._capacity}")
        prompt = np.asarray(prompt, np.int32)
        # ``ptoks`` is the prefix-index key; handle() passes it from the
        # request's own list
        if ptoks is None and self._prefix is not None:
            ptoks = tuple(int(t) for t in prompt[0])
        with self._cond:
            if self._draining:
                raise ReplicaUnavailableError(self.name)
            if not self._free and \
                    len(self._pending) + len(self._prefilling) \
                    >= self.ecfg.max_waiting:
                raise ReplicaUnavailableError(self.name)
            sid = f"{self._tag}:{self._next_sid}"
            self._next_sid += 1
            sess = _EngineSession(sid, prompt, self._lock,
                                  seq_base=seq_base, rid=rid)
            sess.ptoks = ptoks or ()
            # LRU bound on ABANDONED sessions: evict the oldest
            # slot-less finished session (ended clients pop themselves)
            while len(self.sessions) >= max_sessions:
                victim = next((s for s in self.sessions.values()
                               if s.slot is None and s.done), None)
                if victim is None:
                    break
                self.sessions.pop(victim.sid)
            self.sessions[sid] = sess
            self._prefilling.append(sess)
            self._ensure_thread()
            self._cond.notify_all()
            deadline = time.monotonic() + \
                max(1.0, self.ecfg.admission_timeout_s)
            while not sess.ready and sess.error is None \
                    and not sess.shed and not sess.done:
                left = deadline - time.monotonic()
                if left <= 0 or self._shutdown:
                    sess.done = True
                    sess.ended = True
                    self.sessions.pop(sid, None)
                    raise ReplicaUnavailableError(self.name)
                self._cond.wait(min(left, 1.0))
            if sess.shed:     # drain began mid-admission: typed shed,
                raise ReplicaUnavailableError(self.name)  # client resumes elsewhere
            if sess.error is not None:
                raise RuntimeError(sess.error)
            if not sess.ready:   # reaped/force-ended mid-admission
                self.sessions.pop(sid, None)
                raise ReplicaUnavailableError(self.name)
            reply = {"sid": sid, "token": [sess.first_tok],
                     "seq": seq_base}
            if sess.done:
                reply["done"] = True  # prompt/replay prefix filled the cache
        return reply

    def next_chunk(self, sid: str, max_tokens: int = 16,
                   timeout_s: Optional[float] = None) -> Dict[str, Any]:
        """Drain up to ``max_tokens`` buffered tokens (blocking until at
        least one is available, the session finishes, or the timeout).
        Once one token is buffered, lingers ``chunk_linger_s`` for the
        chunk to fill so one RPC round trip carries many tokens."""
        max_tokens = max(1, int(max_tokens))
        deadline = time.monotonic() + (timeout_s if timeout_s is not None
                                       else self.ecfg.chunk_timeout_s)
        linger_deadline = None
        with self._cond:
            sess = self.sessions.get(sid)
            if sess is None:
                return {"error": f"unknown session {sid!r} (ended, "
                                 f"evicted, or never started)"}
            sess.last_poll = time.monotonic()
            sess.want = max_tokens
            while True:
                if sess.error is not None:
                    return {"error": sess.error, "done": True}
                if self._draining:
                    break   # hand off what's buffered, don't wait
                if len(sess.queue) >= max_tokens or \
                        (sess.queue and sess.done):
                    break
                now = time.monotonic()
                if sess.queue:
                    if linger_deadline is None:
                        linger_deadline = now + self.ecfg.chunk_linger_s
                    if now >= linger_deadline:
                        break
                    wait = min(linger_deadline, deadline) - now
                else:
                    if sess.done:
                        return {"tokens": [], "done": True,
                                "seq": sess.seq}
                    wait = deadline - now
                if wait <= 0:
                    break
                sess.cond.wait(wait)
            held = len(sess.queue) + sess.unread
            first_seq = sess.seq
            toks = [sess.queue.popleft()
                    for _ in range(min(len(sess.queue), max_tokens))]
            sess.seq += len(toks)
            done = sess.done and not sess.queue
            out = {"tokens": toks, "done": done, "seq": first_seq}
            if self._draining and not done:
                # replica evacuating: deliver the buffered tokens and
                # hand the session over — the failover client re-admits
                # it (teacher-forced resume) on a healthy replica, and
                # popping it here lets the controller's migration wait
                # see the live-session count drain to zero
                out["migrating"] = True
                sess.done = True
                sess.ended = True
                self.sessions.pop(sid, None)
            if (toks and held >= self.ecfg.token_queue_depth) \
                    or "migrating" in out:
                # the drain un-paused a slot whose queue was full (or
                # handed the session off): the loop may be waiting
                self._cond.notify_all()
        return out

    def end(self, sid: str) -> bool:
        with self._cond:
            sess = self.sessions.pop(sid, None)
            if sess is None:
                return False
            sess.ended = True
            sess.done = True
            sess.wake()
            self._cond.notify_all()   # engine loop vacates the slot
        return True

    def stats(self) -> Dict[str, Any]:
        with self._cond:
            return {"max_slots": self.ecfg.max_slots,
                    "occupied_slots": len(self._slots),
                    "waiting": len(self._pending),
                    "prefilling": len(self._prefilling),
                    "sessions": len(self.sessions),
                    "live_sessions": self._live_locked(),
                    "draining": self._draining,
                    "reaped": self.reaped,
                    "steps": self.steps, "tokens": self.tokens,
                    # fused steps dispatched before the step ahead of
                    # them was read
                    "steps_ahead": self.ahead["steps_ahead"],
                    "prefill_chunks": self.prefill_chunks,
                    # the programs that ran them (fewer where lanes
                    # engaged), and the lanes of one
                    "prefill_programs": self.prefill_programs,
                    "prefill_lanes": self._n_lanes,
                    # the cache rows their attention moved, and those a
                    # real query of theirs saw
                    "chunk_rows_fetched": self.chunk_rows_fetched,
                    "chunk_rows_read": self.chunk_rows_read,
                    # the rows of each (`prefill_chunk_width`)
                    "prefill_chunk_tokens":
                        self.ecfg.prefill_chunk_tokens,
                    # ... those that carried a prompt's remainder, and
                    # the padding rows they computed for nothing
                    "prefill_tails": self.prefill_tails,
                    "prefill_pad_tokens": self.prefill_pad_tokens,
                    # decode_step / cache_insert / prefill_chunk
                    # dispatches that did NOT consume the cache they
                    # were given (0 while donation engages)
                    "cache_copies": self.cache_copies,
                    # decode steps' routing, summed over expert layers
                    # (zeros for a model without a no-drop expert layer)
                    "moe": dict(self.moe, layers=self._moe_layers,
                                experts=self.cfg.n_experts_held),
                    "cache": self._cache_stats(),
                    # every distinct program shape this engine has
                    # dispatched — a compile-storm regression (one
                    # program per prompt/resume length) shows up here
                    # as a count growing with traffic instead of
                    # staying O(1)
                    "program_shapes": sorted(
                        "%s:%s" % (k[0], "x".join(str(d) for d in k[1:]))
                        for k in self._shapes),
                    "distinct_program_shapes": len(self._shapes),
                    "prefix": dict(
                        (self._prefix.stats() if self._prefix is not None
                         else {"entries": 0, "hits": 0, "misses": 0,
                               "hit_rate": None, "tokens_matched": 0}),
                        applied_hits=self.prefix_hits,
                        tokens_reused=self.prefix_tokens_reused),
                    # data-plane flight instruments: per-program
                    # dispatch/compile/MFU ledger + phase attribution
                    "device_profile": self._prof.snapshot(),
                    "phase_totals": self.phase_totals(),
                    # how often the engine thread waited for the lock or
                    # sat in a stalled read, and this process's garbage
                    # collections and late wake-ups (`tracing.host_totals`)
                    "waits": self._waits()}

    def _waits(self) -> Dict[str, int]:
        from ..util import tracing
        host = tracing.host_totals()
        return dict(self.waits, gc_collections=host["gc_collections"],
                    late_wakeups=host["late_wakeups"])

    def _cache_stats(self) -> Dict[str, int]:
        """Bytes of the slot cache by state kind (``bytes_full``: the
        arrays that hold ``max_len`` rows a slot; ``bytes_ring``: the
        window layers' rings; ``bytes_state``: the conv layers' states;
        ``bytes_index``: an indexer's keys, ``bytes_delta``: KDA layers'
        float32 states and convolution inputs, ``bytes_ssm``: state-space
        mixers', where the model has them),
        what ONE further position of a slot costs (the full arrays' bytes
        a row: a ring and a state grow with nothing), and the rows and
        bytes the decode steps read, moved and wrote
        (`models.CacheTraffic.STEP_SUMS`); zeros until the first session
        allocates the cache."""
        kinds = self._cache_bytes(self._cache or {})
        return {"bytes": sum(kinds.values()),
                **{"bytes_" + kind: n for kind, n in kinds.items()},
                "bytes_per_position":
                    (kinds["full"] + kinds.get("summary", 0)
                     + kinds.get("index", 0))
                    // (self.ecfg.max_slots * self.max_len),
                **self.rows}

    def phase_totals(self) -> Dict[str, float]:
        """Cumulative serve-phase seconds — the serve_breakdown
        attribution sources.  queue/admission/first_token come from
        per-session marks (first_token: enqueued -> the first token
        exists); prefill/decode_dispatch are the profiler's per-program
        dispatch walls (engine-thread occupancy, which is what a token
        actually waits on), prefill_tail the part of prefill spent in
        the chunk programs that carried a prompt's REMAINDER (fewer
        real tokens than the chunk holds); schedule/admit_host/dispatch/
        readback/publish are the engine thread's own phases, the
        seconds of its ``engine:`` spans, and schedule_cpu the thread's
        own CPU seconds of ``schedule`` (wall less CPU: runnable and
        not running);
        lock_wait is what the thread waited for the engine's lock
        (INSIDE whichever phase was open, and at a turn's top under
        none), long_read the step reads of `_LONG_READ_S` or more
        (inside readback); gc and late_wakeup are this PROCESS's
        garbage collections and its watch thread's late wake-ups
        (`tracing.host_totals`)."""
        from ..util import tracing
        wall = self._prof.wall_seconds()
        prefill = sum(wall.get(p, 0.0)
                      for p in ("prefill_chunk", "prefix_gather"))
        decode = sum(wall.get(p, 0.0)
                     for p in ("decode_step", "cache_insert"))
        out = {k: round(v, 6) for k, v in self.phase_s.items()}
        out["prefill"] = round(prefill, 6)
        out["decode_dispatch"] = round(decode, 6)
        host = tracing.host_totals()
        out["gc"] = round(host["gc_s"], 6)
        out["late_wakeup"] = round(host["late_wakeup_s"], 6)
        return out

    def _live_locked(self) -> int:
        """Sessions a client may still come back for (not `end`ed):
        the controller's drain wait counts these toward zero before
        stopping the replica."""
        return sum(1 for s in self.sessions.values() if not s.ended)

    def begin_drain(self) -> int:
        """Enter drain mode: shed new starts/resumes with the typed
        ReplicaUnavailableError, stop stepping, and hand every live
        session off on its next `next_chunk` poll (buffered tokens are
        still delivered, stamped with a ``migrating`` flag that sends
        the failover client to a healthy replica).  Sessions still
        mid-prefill are shed the same typed way — their `start` caller
        has no sid yet, so the shed IS the handoff (the failover client
        replays the journal elsewhere).  Returns the number of sessions
        awaiting handoff."""
        with self._cond:
            self._draining = True
            for sess in self._prefilling:
                sess.shed = True
                sess.done = True
                sess.ended = True
                sess.pcache = sess.plogits = None
                self.sessions.pop(sess.sid, None)
            self._prefilling.clear()
            n = self._live_locked()
            self._wake_all_locked()   # blocked next_chunk waits too
        return n

    def live_sessions(self) -> int:
        with self._cond:
            return self._live_locked()

    def shutdown(self) -> None:
        """Stop the loop and, from any thread but the loop's own, wait (at
        most 5 s) for it to end: what the engine held on the device is
        released by then (`_thread_main`), so a caller that loads new
        weights next never holds two models' at once."""
        with self._cond:
            self._shutdown = True
            self._wake_all_locked()
        t = self._thread
        if t is not None and t is not threading.current_thread():
            t.join(timeout=5.0)

    def _wake_all_locked(self) -> None:
        """The loop, the callers in `start` and every session's caller
        in `next_chunk`: for what changes under all of them at once."""
        self._cond.notify_all()
        for sess in self.sessions.values():
            sess.cond.notify_all()

    # ------------------------------------------------------------ engine loop

    def _ensure_thread(self) -> None:
        if self._thread is None or not self._thread.is_alive():
            _ENGINES.add(self)
            self._thread = threading.Thread(
                target=self._thread_main, daemon=True,
                name=f"decode-engine:{self.name}")
            self._thread.start()

    def _thread_main(self) -> None:
        """The loop, and after a `shutdown` the release of what it held
        on the device.  The engine's jitted closures refer back to it, so
        the object itself lives until the cycle collector runs: a replica
        that loads new weights after shutting an engine down (the
        benchmark's outputs check does, seed after seed) would otherwise
        hold the old model's weights and cache beside the new."""
        try:
            self._loop()
        finally:
            if self._shutdown:
                self.params = None
                self._cache = self._pool = None
                self._carry = self._flight = self._active_dev = None

    def _reap_locked(self) -> None:
        """Vacate slots of ended/finished sessions (between steps), and
        evict sessions whose client stopped polling: an abandoned stream
        (client crashed, never sent `end`) would otherwise decode to its
        queue bound and then hold a slot plus session-table memory
        forever."""
        ttl = getattr(self.ecfg, "session_idle_ttl_s", 0.0) or 0.0
        if ttl > 0:
            now = time.monotonic()
            for sid, sess in list(self.sessions.items()):
                if not sess.ended and now - sess.last_poll > ttl:
                    sess.done = True      # slot vacated just below
                    sess.ended = True
                    sess.wake()
                    self.sessions.pop(sid, None)
                    self.reaped += 1
        for slot, sess in list(self._slots.items()):
            if sess.done:
                del self._slots[slot]
                sess.slot = None
                self._free.append(slot)
                # the prefix index KEEPS a freed slot's entry: nothing
                # writes rows below its pos until the slot is
                # reassigned (inactive slots only scribble AT pos,
                # which is past any matchable prefix), so an ended
                # session's system prompt stays a warm donor until the
                # slot is actually reclaimed by a new admission

    def _admit_locked(self) -> List[Tuple[_EngineSession, Any, int]]:
        admitted = []
        if self._draining:
            return admitted   # evacuating: no new slot occupancy
        while self._free and self._pending:
            sess = self._pending.pop(0)
            if sess.ended or sess.done:
                sess.pcache = None
                continue              # ended while waiting
            slot = self._free.pop()
            sess.slot = slot
            self._slots[slot] = sess
            if sess.t_ready is not None:   # admission phase: first
                self.phase_s["admission"] += \
                    time.monotonic() - sess.t_ready  # token -> slot
            if self._prefix is not None:
                # slot reclaim IS the eviction point: the insert below
                # replaces whatever prefix the slot advertised before
                # (its rows are about to be overwritten by
                # cache_insert_slot)
                self._prefix.evict(slot)
                self._donors.pop(slot, None)
                if sess.ptoks:
                    self._prefix.insert(sess.ptoks, slot)
                    self._donors[slot] = sess
            admitted.append((sess, sess.pcache, slot))
            sess.pcache = None
        return admitted

    def _collect_locked(self) -> List[_EngineSession]:
        """Slots decoding THIS step: live sessions with a position left
        in the cache and queue room, the token of a step still in flight
        counted.  A draining engine stops stepping — every live session
        is being handed to a healthy replica, and the replay there
        regenerates anything this engine would have decoded."""
        if self._draining:
            return []
        return [s for s in self._slots.values()
                if not s.done and s.pos < self.max_len and
                len(s.queue) + s.unread < self.ecfg.token_queue_depth]

    def _maybe_push_metrics(self, force: bool = False) -> None:
        """Fire-and-forget occupancy/waiting/prefix sample to this
        worker's nodelet (``serve_metrics`` notify): the nodelet folds
        it into per-(deployment, replica) gauges in its OWN registry,
        which the metrics-history ring samples — that is how engine
        occupancy becomes the per-deployment time series the autoscale
        loop and ``ray-tpu top`` read (worker registries are never
        scraped directly).  Engine thread only; never blocks on the
        RPC."""
        from ..core.config import GlobalConfig
        iv = getattr(GlobalConfig, "serve_engine_metrics_interval_s", 0.5)
        if iv is None or iv <= 0:
            return
        now = time.monotonic()
        if not force and now - self._last_metrics_push < iv:
            return
        self._last_metrics_push = now
        # every key here is folded by nodelet._h_serve_metrics (the
        # rpc-payload-contract rule flags unread wire bytes); prefix
        # counters travel cumulative and the nodelet folds the delta
        payload = {"deployment": self.name, "replica": self._tag,
                   "occupied": len(self._slots),
                   "max_slots": self.ecfg.max_slots,
                   "waiting": len(self._pending) + len(self._prefilling),
                   "prefix_hits": self.prefix_hits,
                   "prefix_tokens_reused": self.prefix_tokens_reused,
                   # data-plane flight instruments (all cumulative;
                   # nodelet delta-folds): per-program dispatch/compile
                   # ledger + MFU, tokens generated, phase attribution,
                   # and the distinct-shape count the compile-storm
                   # detector watches
                   "tokens": self.tokens,
                   "distinct_program_shapes": len(self._shapes),
                   "device_profile": self._prof.snapshot(),
                   "phase_totals": self.phase_totals()}
        try:
            import asyncio

            from ..core.worker_runtime import current_worker_runtime
            rt = current_worker_runtime()
            if rt is not None and rt._loop is not None:
                asyncio.run_coroutine_threadsafe(
                    rt.nodelet.notify("serve_metrics", payload), rt._loop)
        except Exception:
            pass   # driver-local engine (tests) or torn-down runtime

    def _shape_seen(self, kind: str, *dims) -> None:
        """Record one dispatched program shape (engine thread only) —
        surfaces in stats() so a per-path compile storm is visible."""
        self._shapes.add((kind,) + tuple(int(d) for d in dims))

    def _prefix_exact(self, donor: int, depth: int, n: int) -> bool:
        """Whether slot ``donor`` still holds what a session of ``n``
        prompt tokens seeded with its first ``depth`` positions attends:
        `models.prefix_holds`' rule, by the cache's state kinds, of where
        the session the prefix index advertises there stands now."""
        from ..models import prefix_holds
        sess = self._donors.get(donor)
        return prefix_holds(
            self.cfg, None if sess is None else sess.pos, depth, n,
            self.ecfg.prefill_chunk_tokens, self._capacity)

    def _seed_cache(self, sess: _EngineSession) -> None:
        """The batch-1 cache a session's prompt starts from: the longest
        prefix it shares with a live slot's prompt where one is on offer
        (``poff`` = its depth), else zeros."""
        import jax.numpy as jnp

        from ..models import init_kv_cache
        if self._prefix is not None and sess.ptoks:
            # shared-prefix admission: the longest prefix this prompt
            # shares with a LIVE slot's prompt is already in the slot
            # cache — copy those K/V rows (one compiled gather, slot +
            # depth traced) and prefill only the unshared suffix.  Cap at
            # len-1: the last prompt token's logits must be recomputed to
            # emit the first token.
            donor, depth = self._prefix.longest_match(
                sess.ptoks, cap=len(sess.ptoks) - 1)
            # an indexed donor is valid whether its session is still
            # decoding or ended: entries are only replaced when the slot
            # is reassigned, and freed slots' rows below the match depth
            # are never written in between
            if donor is not None and \
                    depth >= max(1, self.ecfg.prefix_cache_min_tokens) \
                    and self._prefix_exact(donor, depth, len(sess.ptoks)):
                from ..core.runtime_metrics import (
                    SERVE_PREFIX_HITS, SERVE_PREFIX_TOKENS_REUSED)
                sess.pcache = self._gather(self._cache, jnp.int32(donor),
                                           jnp.int32(depth))
                sess.poff = depth
                with self._loop_lock:   # stats() reads these counters
                    self.prefix_hits += 1
                    self.prefix_tokens_reused += depth
                self._shape_seen("prefix_gather", 1)
                SERVE_PREFIX_HITS.inc(tags={"deployment": self.name})
                SERVE_PREFIX_TOKENS_REUSED.inc(
                    depth, tags={"deployment": self.name})
                return
        sess.pcache = init_kv_cache(self.cfg, 1, self.max_len)

    def _lane_sums(self) -> Dict[str, int]:
        """What an `engine:lanes` span sums: chunk programs, the chunks they
        consumed, the cache rows their attention moved and, of those, the
        rows a real query saw."""
        return {"programs": self.prefill_programs,
                "chunks": self.prefill_chunks,
                "chunk_rows_fetched": self.chunk_rows_fetched,
                "chunk_rows_read": self.chunk_rows_read,
                "rows_fed": self.rows_fed, "tail_rows": self.tail_rows}

    def _count_chunks(self, riders: List[Tuple[_EngineSession, int]],
                      wall: float, lanes: int = 1) -> None:
        """ONE chunk program of ``lanes`` lanes that took ``wall`` seconds of
        the engine thread and carried ``riders``: (session, real rows of its
        chunk)."""
        from ..core.runtime_metrics import SERVE_PREFILL_CHUNKS
        chunk = self.ecfg.prefill_chunk_tokens
        now = time.monotonic()
        for sess, _ in riders:
            if sess.t_pf is None:      # queue phase ends at the first
                sess.t_pf = now        # chunk program of the prompt
                self.phase_s["queue"] += sess.t_pf - sess.t_enq
        # a prompt's remainder, padded: its share of the program
        tails = [n for _, n in riders if n < chunk]
        self.phase_s["prefill_tail"] += wall * len(tails) / len(riders)
        self._prof.note_tokens("prefill_chunk", sum(n for _, n in riders))
        # (a rider's `poff` is already past the chunk it rode)
        moved = [self._traffic.chunk(sess.poff - n, n) for sess, n in riders]
        with self._loop_lock:   # stats() reads these counters
            self.prefill_programs += 1
            self.prefill_chunks += len(riders)
            self.prefill_tails += len(tails)
            self.prefill_pad_tokens += sum(chunk - n for n in tails)
            self.chunk_rows_fetched += sum(f for f, _ in moved)
            self.chunk_rows_read += sum(r for _, r in moved)
            self.rows_fed += lanes * chunk
            self.tail_rows += self._traffic.tail_rows(lanes)
        self._lanes_span = self._sums_span(
            "engine:lanes", "lanes", self._lane_sums(), self._lanes_span)
        SERVE_PREFILL_CHUNKS.inc(len(riders),
                                 tags={"deployment": self.name})

    def _prefill_advance(self, sess: _EngineSession) -> Optional[int]:
        """Run ONE fixed-shape chunk program of ONE joining session's
        prompt over its own batch-1 cache on the engine thread —
        interleaved between shared decode steps, so admission stalls live
        streams by at most one chunk interval instead of a whole prompt.
        Returns the session's first token, still on the device, once the
        prompt is fully consumed, else None."""
        import jax.numpy as jnp

        from ..models.generate import prefill_chunk_step
        if sess.pcache is None:
            self._seed_cache(sess)
        chunk = self.ecfg.prefill_chunk_tokens
        wall0 = self._prof.wall_of("prefill_chunk")
        # ONE shape per model: whole chunks, then the remainder as one
        # more, padded, its count of real tokens a traced argument
        sess.plogits, sess.pcache, sess.poff, n_valid = prefill_chunk_step(
            self._chunk, self.params, sess.prompt, sess.poff, sess.pcache,
            self.cfg, chunk=chunk, capacity=self._capacity)
        self._shape_seen("prefill_chunk", 1, chunk)
        self._count_chunks([(sess, n_valid)],
                           self._prof.wall_of("prefill_chunk") - wall0)
        if sess.poff < int(sess.prompt.shape[1]):
            return None
        return self._greedy(sess.plogits).astype(jnp.int32)[0]

    # -------------------------------------------------- lanes: one chunk
    # program for several joining sessions

    def _free_lanes_locked(self) -> None:
        """Lanes whose session prefills no longer (ready, ended, reaped,
        shed, failed) are free, and with nobody left prefilling the lane
        cache goes: an idle engine holds what it held without lanes."""
        for lane, sess in enumerate(self._lane_sess):
            if sess is not None and sess not in self._prefilling:
                self._lane_sess[lane] = sess.lane = None
        if not self._prefilling:
            self._pool = None

    def _enter_lane(self, sess: _EngineSession, lane: int) -> None:
        """``sess`` takes ``lane``: the batch-1 cache it has (a lone
        session overtaken by a second one) or starts from (`_seed_cache`:
        a donor's prefix, or zeros, which also clear what the lane's last
        holder left in a conv state) goes into the lane by the slot
        insert, its ``pos`` with it."""
        import jax.numpy as jnp

        from ..models import init_slot_cache
        if sess.pcache is None:
            self._seed_cache(sess)
        if self._pool is None:
            self._pool = init_slot_cache(self.cfg, self._n_lanes,
                                         self.max_len)
        self._pool = self._insert(self._pool, sess.pcache, jnp.int32(lane))
        self._shape_seen("lane_insert", self._n_lanes)
        sess.pcache = None
        sess.lane, self._lane_sess[lane] = lane, sess

    def _leave_lane(self, sess: _EngineSession) -> None:
        """``sess``'s lane as the batch-1 cache that `_prefill_advance`
        and `_admit_locked` take (the slot gather, truncated to what the
        session has consumed); the lane is free at once."""
        import jax.numpy as jnp
        sess.pcache = self._gather(self._pool, jnp.int32(sess.lane),
                                   jnp.int32(sess.poff))
        self._shape_seen("lane_gather", self._n_lanes)
        self._lane_sess[sess.lane] = sess.lane = None

    def _fail_prefill(self, sess: _EngineSession, e: Exception) -> None:
        with self._loop_lock:
            sess.error = f"chunked prefill failed: {e!r}"
            sess.done = True
            sess.ready = True
            sess.pcache = sess.plogits = None
            self._cond.notify_all()

    def _lanes_advance(self, prefills: List[_EngineSession], fi
                       ) -> List[Tuple[_EngineSession, Any, int]]:
        """ONE chunk program for every session that holds a lane, after
        the sessions without one have taken the free lanes in arrival
        order (the others wait in `_prefilling` with no cache at all)
        → the sessions whose prompt it consumed, each with the program's
        first tokens (on the device) and where its own stands in them,
        and, as ``pcache``, its lane gathered out.  A session that
        cannot enter or move (a window that would rewind a conv state)
        fails alone; a program that raises fails the sessions in it and
        takes the lane cache with it (it was donated)."""
        import jax.numpy as jnp

        from ..models.generate import _window_of, prefill_lanes_step
        chunk = self.ecfg.prefill_chunk_tokens
        for sess in prefills:
            try:
                if sess.lane is None:
                    if None not in self._lane_sess:
                        continue
                    self._enter_lane(sess, self._lane_sess.index(None))
                _window_of(self.cfg, int(sess.prompt.shape[1]), sess.poff,
                           chunk, self._capacity)
            except Exception as e:
                if sess.lane is not None:
                    self._lane_sess[sess.lane] = sess.lane = None
                self._fail_prefill(sess, e)
        riders = [s for s in self._lane_sess if s is not None]
        if not riders:
            return []
        wall0 = self._prof.wall_of("prefill_chunk")
        try:
            self._chaos_site("serve.prefill_chunk", fi)
            logits, self._pool, moved = prefill_lanes_step(
                self._chunk_lanes, self.params,
                [None if s is None else (s.prompt, s.poff)
                 for s in self._lane_sess], self._pool, self.cfg,
                chunk=chunk, capacity=self._capacity)
        except Exception as e:
            self._pool = None
            for sess in riders:
                self._lane_sess[sess.lane] = sess.lane = None
                self._fail_prefill(sess, e)
            return []
        self._shape_seen("prefill_chunk", self._n_lanes, chunk)
        for sess in riders:
            sess.poff = moved[sess.lane][0]
        self._count_chunks([(s, moved[s.lane][1]) for s in riders],
                           self._prof.wall_of("prefill_chunk") - wall0,
                           self._n_lanes)
        done = [s for s in riders if s.poff >= int(s.prompt.shape[1])]
        if not done:
            return []
        firsts = self._greedy(logits)              # ONE read, later
        ready = [(sess, firsts, sess.lane) for sess in done]
        for sess in done:
            self._leave_lane(sess)
        return ready

    def _warm_lanes(self) -> None:
        """Before the first session is served, once EVERY program and
        shape the lane path uses: the lane cache's zeros, a batch-1 cache
        inserted into a lane, the lanes program (every lane standing: it
        changes nothing), the first tokens' read, a lane gathered out.
        Whoever warms a replica sends it one session, which cannot reach
        a path that takes two; with this a second prompt that joins a
        prefilling one compiles nothing.  An engine that cannot run the
        lane path (no room for the lane cache) goes on with one program a
        session."""
        import numpy as np

        import jax
        import jax.numpy as jnp

        from ..models import init_kv_cache, init_slot_cache
        from ..models.generate import prefill_lanes_step
        from ..util import tracing
        if self._n_lanes < 2:
            return

        def bare(fn):    # not traffic: the profiler's ledgers stay its own
            return getattr(fn, "_rt_profiled_inner", fn)

        t0 = time.time()
        try:
            pool = bare(self._insert)(
                init_slot_cache(self.cfg, self._n_lanes, self.max_len),
                init_kv_cache(self.cfg, 1, self.max_len), jnp.int32(0))
            logits, pool, _ = prefill_lanes_step(
                bare(self._chunk_lanes), self.params,
                [None] * self._n_lanes, pool, self.cfg,
                chunk=self.ecfg.prefill_chunk_tokens,
                capacity=self._capacity)
            np.asarray(self._greedy(logits))
            jax.block_until_ready(
                bare(self._gather)(pool, jnp.int32(0), jnp.int32(0)))
        except Exception as e:
            self._n_lanes, self._lane_sess = 0, []
            tracing.record_span(
                f"serve_lanes_off::{self.name}", "serve", t0, time.time(),
                error=repr(e), deployment=self.name)

    def _chaos_site(self, site: str, fi) -> None:
        """Chaos site ``serve.<what>``: an armed rule sleeps here
        (``delay``) or raises."""
        act = None if fi.ACTIVE is None else fi.ACTIVE.point(
            site, self.name)
        if act is None:
            return
        if act["action"] in ("delay", "latency"):
            time.sleep(max(0.0, act["delay_s"]))
        else:
            raise RuntimeError(f"chaos: injected {site[6:]} failure for "
                               f"{self.name}")

    def _loop(self) -> None:
        import numpy as np

        from ..models import CacheTraffic, init_slot_cache
        from ..util import fault_injection as fi
        from ..util import tracing
        if self._cache is None:
            self._cache = init_slot_cache(self.cfg, self.ecfg.max_slots,
                                          self.max_len)
        # what a fused step and a chunk a lane read, move and write of it,
        # from positions: the cache's shapes and this backend's kernels say
        self._traffic = CacheTraffic(self._cache, self.cfg,
                                     self.ecfg.prefill_chunk_tokens)
        slots = self.ecfg.max_slots
        self._carry = self._fresh_carry()
        self._warm_lanes()
        # the ring spans' sums count from here, not from the warm-up
        for last in (self._moe_span, self._rows_span, self._ahead_span,
                     self._lanes_span):
            last["t"] = time.time()

        def phase(name: str):
            """One of the engine thread's flat, non-overlapping phases:
            a host annotation `engine:<name>` in a profiler trace and
            seconds in `phase_s`; none is open while the thread waits
            with nothing to do.  `schedule` holds the lock throughout
            and dispatches nothing, so its CPU seconds are kept beside
            its wall seconds (`schedule_cpu`): the rest is time the
            thread stood runnable and did not run.  That phase alone:
            `time.thread_time()` is a system call, 6 us on the chip's
            host (PERF.md section 6, PR 51)."""
            return tracing.span("engine:" + name, "serve",
                                into=(self.phase_s,
                                      self._THREAD_PHASES[name]),
                                cpu=name == "schedule")

        while True:
            with self._loop_lock:
                while not self._shutdown:
                    with phase("schedule"):
                        self._reap_locked()
                        self._maybe_push_metrics()
                        self._prefilling = [
                            s for s in self._prefilling
                            if not (s.ready or s.done or s.ended
                                    or s.shed)]
                        self._free_lanes_locked()
                        admitted = self._admit_locked()
                        prefills = ([] if self._draining
                                    else list(self._prefilling))
                        batch = self._collect_locked()
                        active = np.zeros(self.ecfg.max_slots, bool)
                        for s in batch:
                            active[s.slot] = True
                    if admitted or prefills or batch \
                            or self._flight is not None:
                        break
                    self._cond.wait(0.5)
                if self._shutdown:
                    return
            # ---- device work, OUTSIDE the lock (nobody else touches
            # the slot cache, and client ops must not stall on compute)
            handed = []
            if admitted or prefills:
                with phase("admit"):
                    handed = self._admit_and_prefill(admitted, prefills, fi)
            step = new_toks = failed = None
            try:
                if batch:
                    with phase("dispatch"):
                        step = self._dispatch(
                            batch, active, admitted,
                            alone=not (admitted or prefills))
            except Exception as e:
                failed = e
            if handed:     # the step is queued behind the chunk programs
                with phase("admit"):
                    self._hand_over(handed)
            try:
                if failed is not None:
                    raise failed
                # ONE STEP AHEAD: what is read now is the step before the
                # one just queued, and the chip works on through the read,
                # the publish and the next schedule
                step, self._flight = self._flight, step
                if step is not None:
                    with phase("readback"):
                        new_toks = self._read(step, fi)
                    if self._moe_layers:
                        self._count_moe(new_toks[slots:], len(step.batch))
                    self._count_rows(step.rows)
            except Exception as e:
                # seen at the dispatch or one read late: either way the
                # step queued behind the one that raised goes with it
                self._fail_slots(f"decode engine step failed: {e!r}")
                continue
            if step is not None:
                with phase("publish"):
                    self._publish(step.batch, new_toks)

    def _fresh_carry(self):
        """The carry before any step: zeros on the device (a joining
        slot's first token is written into it; a step's routing counts
        ride behind its tokens, `fused_step`)."""
        import jax.numpy as jnp
        return jnp.zeros(self.ecfg.max_slots + (
            self.cfg.load_counts if self._moe_layers else 0), jnp.int32)

    def _dispatch(self, batch, active, admitted, alone: bool) -> _Step:
        """Queue one fused step over ``batch`` and keep, at DISPATCH
        time, what the host knows of it: each live slot's position moves
        on by one and one more of its tokens is unread, so the next
        schedule (``max_len`` ends, the queue bound, `_prefix_exact`, the
        donors' positions) sees the cache as the chip will leave it.
        Only the token VALUES come later, with the read.

        A change of membership drains nothing: a slot that left or
        paused is a new ``active`` mask (its carry entry stays put,
        `fused_step` passes an idle slot's token through), a slot that
        joined (``admitted`` this turn) is its first token, a host-known
        int, written into the carry behind its `cache_insert_slot`.  ``alone``: no other
        program was queued since the last step (the device-time sample
        needs to know what ran between two reads)."""
        import numpy as np

        import jax.numpy as jnp
        if admitted:
            firsts = np.full(self._carry.shape[0], -1, np.int32)
            for sess, _, slot in admitted:
                firsts[slot] = sess.last_tok
            self._carry = self._join(self._carry, firsts)
        key = active.tobytes()
        if key != self._active_key:
            self._active_dev, self._active_key = jnp.asarray(active), key
        # at the positions BEFORE this step; and what it writes
        rows = self._traffic.step([s.pos for s in batch])
        flight = self._flight
        with self._loop_lock:
            for s in batch:
                s.pos += 1
                s.unread += 1
            self.ahead["steps"] += 1
            self.ahead["steps_ahead"] += flight is not None
        self._ahead_span = self._sums_span(
            "engine:ahead", "ahead", self.ahead, self._ahead_span)
        start = None
        if alone:
            start = time.perf_counter() \
                if flight is None or flight.out.is_ready() else _BEHIND
        self._carry, self._cache = self._step(
            self.params, self._carry, self._cache, self._active_dev,
            cfg=self.cfg)
        self._shape_seen("decode_step", self.ecfg.max_slots)
        return _Step([(s, s.slot) for s in batch], self._carry, rows,
                     start)

    def _read(self, step: _Step, fi):
        """``step``'s tokens (and routing counts) on the host.  Where the
        read has to wait for the chip and the host can tell when the chip
        started on the step, the time between is one step's device time:
        the profiler's sample, taken where the loop waits anyway.  A
        device fault surfaces here, one read after its dispatch (chaos
        site ``serve.decode_step``)."""
        import numpy as np

        from ..util import tracing
        began = time.perf_counter()
        late = tracing.host_totals()["late_wakeups"]
        self._chaos_site("serve.decode_step", fi)
        waited = not step.out.is_ready()
        new_toks = np.asarray(step.out)
        now = time.perf_counter()
        if now - began >= self._LONG_READ_S:
            # a stall.  With no late wake-up of the watch thread beside
            # it, the device or the transfer; with one, the interpreter
            # was held or the host did not run the process
            self.phase_s["long_read"] += now - began
            self.waits["long_reads"] += 1
            wall = time.time()
            tracing.record_span(
                "engine:long_read", "stall", wall - (now - began), wall,
                deployment=self.name, step=self.steps,
                waited_ms=round(1e3 * (now - began), 3),
                live=len(step.batch),
                late_wakeups=tracing.host_totals()["late_wakeups"] - late)
        start = step.start
        if start == _BEHIND:
            start = self._read_end if self._read_waited else None
        if waited and start is not None:
            self._prof.note_device_seconds("decode_step", now - start)
        self._read_end, self._read_waited = now, waited
        return new_toks

    _MOE_SPAN_S = 2.0

    def _count_moe(self, load, live: int) -> None:
        """One decode step's routing (``live`` rows' worth) into the
        counters, and the sums since the last `moe:load` span into the
        next one when it is due.  A router with identity experts counts
        the pairs that chose one (``zero_pairs``) beside all the pairs its
        live rows ``chosen``: rows x experts a token x layers that route."""
        with self._loop_lock:   # stats() reads these
            self.moe["steps"] += 1
            self.moe["experts_touched"] += int(load[0])
            self.moe["load_max"] += int(load[1])
            self.moe["pairs"] += int(load[2])
            if self.cfg.zero_experts:
                self.moe["zero_pairs"] += int(load[3])
                self.moe["chosen"] += live * self.cfg.expert_top_k \
                    * self._moe_layers
        # a category of its own: the ring keeps a bound a category, and
        # the two `serve` spans of every `next_chunk` call push a span
        # out of a full ring within seconds of a busy window
        self._moe_span = self._sums_span(
            "moe:load", "moe", self.moe, self._moe_span,
            layers=self._moe_layers, experts=self.cfg.n_experts_held)

    def _count_rows(self, rows: Tuple[int, ...]) -> None:
        """A read step's `CacheTraffic.step` into the counters, and the sums
        since the last `cache:rows` span into the next when due."""
        with self._loop_lock:   # stats() reads these
            self.rows["steps"] += 1
            for k, n in zip(self._traffic.STEP_SUMS, rows):
                self.rows[k] += n
        self._rows_span = self._sums_span(
            "cache:rows", "cache", self.rows, self._rows_span,
            lambda: {"bytes_" + kind: n for kind, n in
                     self._cache_bytes(self._cache).items()})

    def _sums_span(self, name: str, category: str, sums: Dict[str, int],
                   last: Dict[str, Any], args=dict, **more
                   ) -> Dict[str, Any]:
        """Every `_MOE_SPAN_S` seconds ONE ring span ``name`` whose
        arguments are ``sums`` since ``last`` (a span a step cost too
        much: PERF.md, PR 25) beside ``args()`` and ``more`` → what the
        next one counts from."""
        from ..util import tracing
        now = time.time()
        if now - last["t"] < self._MOE_SPAN_S:
            return last
        tracing.record_span(
            name, category, last["t"], now, deployment=self.name,
            **args(), **more, **{k: sums[k] - last[k] for k in sums})
        return dict(sums, t=now)

    def _fail_slots(self, error: str) -> None:
        """A donated step raised: the slot cache it was given may be
        gone, and with it every slot's rows, not only the batch's.  Fail
        every session that holds a slot (the reaper frees the slots next
        turn), forget the prefixes the lost rows advertised, and go on
        with a fresh cache and carry.  The step in flight, queued behind
        the one that raised or ahead of the one that could not be
        queued, ran on the same cache: its output is dropped unread, so
        no token of either is published.  Sessions still prefilling or
        waiting for a slot own their batch-1 caches and are untouched."""
        from ..models import init_slot_cache
        with self._loop_lock:
            for sess in self._slots.values():
                sess.error = error
                sess.done = True
                sess.wake()
            if self._prefix is not None:
                for slot in range(self.ecfg.max_slots):
                    self._prefix.evict(slot)
            self._cond.notify_all()
        self._flight = None
        self._carry = self._fresh_carry()
        self._cache = None     # free what is left before allocating anew
        self._cache = init_slot_cache(self.cfg, self.ecfg.max_slots,
                                      self.max_len)

    def _admit_and_prefill(self, admitted, prefills, fi
                           ) -> List[Tuple[_EngineSession, Any, int]]:
        """The host side of admission, on the engine thread outside the
        lock: slot inserts of the sessions `_admit_locked` placed and the
        turn's chunk programs (a prompt is consumed BETWEEN decode steps,
        never ahead of the live batch) → the sessions whose prompt the
        LANES program consumed, for `_hand_over`: that program and the
        gathers behind it are dispatched here and nothing of them read,
        so the turn's decode step goes out behind them first.

        How many programs follows from what the loop sees, no option:
        ONE session prefilling runs the batch-1 chunk program over its own
        cache; TWO OR MORE share ONE program over the lane cache, up to
        `_n_lanes` of them in arrival order (`_lanes_advance`), which
        reads every weight once for the lot; when one is left it goes back
        to a batch-1 cache and the lane cache is dropped, so a lone prompt
        never pays for lanes that stand.  (`_n_lanes` falls to 0 only
        where `_warm_lanes` found no room for the lane cache.)"""
        import jax.numpy as jnp
        for sess, pcache, slot in admitted:
            self._cache = self._insert(self._cache, pcache,
                                       jnp.int32(slot))
        if self._n_lanes >= 2 and len(prefills) >= 2:
            return self._lanes_advance(prefills, fi)
        ready = []
        for sess in prefills:
            try:
                if sess.lane is not None:   # the last of several
                    self._leave_lane(sess)
                self._pool = None           # nobody holds a lane now
                first = self._prefill_advance(sess)
                if first is not None:
                    ready.append((sess, first, ()))
            except Exception as e:
                self._fail_prefill(sess, e)
        # a prompt that prefilled alone is handed over at once, as ever:
        # its caller's first token does not wait for the step's dispatch
        self._hand_over(ready)
        return []

    def _hand_over(self, ready: List[Tuple[_EngineSession, Any, int]]
                   ) -> None:
        """Every session whose prompt this turn's chunk programs consumed
        gets its first token and goes on to wait for a slot.  Here the
        host READS the first tokens (``firsts[at]`` on the device, one
        array a program); for a lanes program that is behind the dispatch
        of the turn's decode step: the chip works on the step while the
        host waits for the chunk program, takes the lock and wakes the
        callers.  A chunk program that failed on the device surfaces in
        this read."""
        import numpy as np

        from ..util import tracing
        firsts = []
        for sess, dev, at in ready:
            try:
                firsts.append((sess, int(np.asarray(dev)[at])))
            except Exception as e:
                self._fail_prefill(sess, e)
        now_mono = time.monotonic()
        now_wall = time.time()
        with self._loop_lock:
            for sess, first in firsts:
                sess.t_ready = now_mono
                self.phase_s["first_token"] += now_mono - sess.t_enq
                # per-request admission span (wall clock, like every
                # lifecycle span): enqueue -> first token
                tracing.record_span(
                    f"serve_admission::{self.name}", "serve",
                    now_wall - (now_mono - sess.t_enq),
                    now_wall, rid=sess.rid, sid=sess.sid,
                    deployment=self.name)
                sess.first_tok = sess.last_tok = first
                sess.pos = sess.poff
                sess.ready = True
                sess.prompt = sess.plogits = None
                if sess.pos >= self.max_len or sess.ended:
                    sess.done = True  # nothing left to decode
                    sess.pcache = None
                else:
                    self._pending.append(sess)
            self._cond.notify_all()

    def _publish(self, batch, new_toks) -> None:
        """After a step's read-back: counters, then under the lock the
        new tokens onto their sessions' queues and the wake-up of the
        callers waiting for them.  ``batch`` is the step's ``(session,
        slot)`` as they were when it was dispatched: by now the session
        may have ended and the slot be another's (its token is dropped)."""
        from ..core.runtime_metrics import (SERVE_DECODE_OCCUPANCY,
                                            SERVE_TOKENS)
        occupancy = len(batch)
        # MFU numerators: useful tokens only (active slots), host-
        # known counts — never a device sync
        self._prof.note_tokens("decode_step", occupancy)
        SERVE_DECODE_OCCUPANCY.observe(occupancy,
                                       {"deployment": self.name})
        SERVE_TOKENS.inc(occupancy, {"deployment": self.name})
        with self._loop_lock:
            self.steps += 1
            self.tokens += occupancy
            for s, slot in batch:
                tok = int(new_toks[slot])
                s.last_tok = tok
                s.unread -= 1      # `pos` moved on at the dispatch
                was_empty = not s.queue
                if not s.ended:
                    s.queue.append(tok)
                if s.pos >= self.max_len and not s.unread:
                    s.done = True  # cache full: reaped next turn
                s.wake(was_empty)


def _host_tokens(prompt) -> Optional[tuple]:
    """Prompt ints straight from the request payload (the prefix-index
    key) — no device round trip.  Returns None when the payload isn't a
    host-side B=1 token list (device arrays fall back to start()'s own
    materialization)."""
    if not isinstance(prompt, (list, tuple)):
        return None
    try:
        p = prompt
        if p and isinstance(p[0], (list, tuple)):
            p = p[0]
        return tuple(int(t) for t in p)
    except (TypeError, ValueError, IndexError):
        return None


class DecodeSessionCore:
    """The decode-session protocol over ONE continuous-batching engine
    and one model: what a replica's ``__call__`` forwards to.

    Protocol (msgpack/JSON-native):
      {"op": "start", "prompt": [S ints] | [[S ints]]} ->
          {"sid": str, "token": [1 int], "seq": 0} (+ {"done": true}
          when the prompt filled the cache)
      {"op": "resume", "prompt": [S ints], "generated": [G ints]} ->
          same shape as a start, with "seq": G — failover
          re-admission: teacher-forced prefix prefill of
          prompt+generated into a fresh engine slot; the returned token
          is exactly the one the uninterrupted session would have
          produced next (greedy decode is deterministic)
      {"op": "next_chunk", "sid": str, "max_tokens": N} ->
          {"tokens": [<=N ints], "done": bool, "seq": first token's
          seq} (+ {"migrating": true} when the replica is draining and
          the session must be resumed elsewhere)
      {"op": "next", "sid": str} -> {"token": [1 int]} (+ {"eos": true}):
          the one-token form of ``next_chunk``
      {"op": "end", "sid": str} -> {"ended": bool}
      {"op": "stats"} -> engine/session counters (tests, dashboards)

    Sids are STRINGS of the form ``<replica_tag>:<n>`` — the prefix is
    the owning replica, which the proxy/router use for sid-sticky
    routing.  A sid the engine does not hold (ended, evicted, never
    started, not a string) gets an ``{"error": ...}`` reply, never an
    exception.  A start with B>1 prompts admits each row as its own
    engine session behind a ``grp:<n>`` sid, whose ``next`` replies
    ``{"token": [B ints]}``; ``max_sessions`` bounds the abandoned
    sessions the engine keeps (LRU).
    """

    def __init__(self, cfg, max_len: int, seed: int = 0,
                 params: Any = None, max_sessions: int = 64,
                 engine: Optional[DecodeEngineConfig] = None):
        """``engine`` is the engine's :class:`DecodeEngineConfig`
        (``None``: its defaults); ``params`` the model's weights
        (``None``: ``init_params(PRNGKey(seed), cfg)``)."""
        import jax

        from ..models import init_params
        if engine is None:
            engine = DecodeEngineConfig()
        if not isinstance(engine, DecodeEngineConfig):
            raise TypeError(f"engine must be a DecodeEngineConfig or None, "
                            f"got {engine!r}")
        self.cfg = cfg
        self.max_len = max_len
        self.max_sessions = max_sessions
        if params is None:
            params, _ = init_params(jax.random.PRNGKey(seed), cfg)
        self.params = params
        self._lock = threading.Lock()
        # B>1 prompt batches: each row is its own engine session; the
        # group keeps a one-reply-per-step shape (sid + [B] tokens)
        self._groups: Dict[str, List[str]] = {}
        self._next_gid = 0
        self._engine_cfg = engine
        self._engine: Optional[ContinuousBatchingEngine] = None

    @property
    def engine(self) -> ContinuousBatchingEngine:
        """The continuous-batching engine, created on first use (slot
        cache memory is only paid by cores that actually serve).
        Creation is locked: two concurrent `start` ops racing the lazy
        init would strand one session in an engine nothing references
        — and hand out colliding ``<tag>:0`` sids."""
        if self._engine is None:
            with self._lock:
                if self._engine is None:
                    name, tag = "decode", "local"
                    try:
                        from .replica import get_replica_context
                        ctx = get_replica_context()
                        name, tag = ctx.deployment, ctx.replica_tag
                    except RuntimeError:
                        pass
                    self._engine = ContinuousBatchingEngine(
                        self.cfg, self.max_len, self.params,
                        self._engine_cfg, name=name, replica_tag=tag)
        return self._engine

    def handle(self, req: Dict[str, Any]) -> Dict[str, Any]:
        import numpy as np
        op = req["op"]
        if op == "start":
            # on the HOST: the engine fills its chunk buffers from it
            prompt = np.asarray(req["prompt"], np.int32)
            if prompt.ndim == 1:
                prompt = prompt[None]
            if prompt.shape[0] == 1:
                return self.engine.start(
                    prompt, self.max_sessions,
                    ptoks=_host_tokens(req["prompt"]),
                    rid=str(req.get("_rid") or ""))
            return self._group_start(prompt, req["prompt"])
        if op == "resume":
            # failover re-admission (serve/failover.py): replay the
            # journal — prompt + every token the client already has —
            # through a teacher-forced prefix prefill into a fresh
            # engine slot, continuing seqs at len(generated)
            prompt = req["prompt"]
            if prompt and isinstance(prompt[0], (list, tuple)):
                prompt = prompt[0]     # batched form: engine is B=1
            generated = list(req.get("generated") or [])
            replay = list(prompt) + generated
            prefix = np.asarray([replay], np.int32)
            return self.engine.start(
                prefix, self.max_sessions, seq_base=len(generated),
                teacher_forced=True,
                ptoks=tuple(int(t) for t in replay),
                rid=str(req.get("_rid") or ""))
        if op == "stats":
            out = {"groups": len(self._groups)}
            if self._engine is not None:
                out["engine"] = self._engine.stats()
            return out
        sid = req.get("sid")
        if isinstance(sid, str) and sid.startswith("grp:"):
            return self._group_op(op, sid)
        # an engine that was never started holds no session either: the
        # lookups below give the engine's own unknown-session replies
        if op == "end":
            return {"ended": self.engine.end(sid)}
        if op == "next_chunk":
            return self.engine.next_chunk(
                sid, req.get("max_tokens", 16), req.get("timeout_s"))
        # op == "next": the one-token form of next_chunk
        out = self.engine.next_chunk(sid, 1)
        if "error" in out:
            return out
        if not out["tokens"]:
            return {"error": f"session {sid!r} finished "
                             f"(cache capacity reached)"}
        reply = {"token": out["tokens"]}
        if out["done"]:
            reply["eos"] = True
        return reply

    def _group_start(self, prompt, raw_prompt=None) -> Dict[str, Any]:
        """B>1 prompts through the ONE data plane: admit each row as
        its own engine session and hand back a group sid whose `next`
        pops one token per member ({sid, token: [B]}).  A member shed
        mid-admission (slots + wait queue full) releases the members
        already admitted and re-raises, so a group is all or nothing."""
        sids, toks = [], []
        try:
            for row in range(int(prompt.shape[0])):
                pt = None
                if raw_prompt is not None:
                    try:
                        pt = _host_tokens([raw_prompt[row]])
                    except (TypeError, IndexError):
                        pt = None
                out = self.engine.start(prompt[row:row + 1],
                                        self.max_sessions, ptoks=pt)
                sids.append(out["sid"])
                toks.extend(out["token"])
        except BaseException:
            for s in sids:
                self.engine.end(s)
            raise
        with self._lock:
            gid = f"grp:{self._next_gid}"
            self._next_gid += 1
            self._groups[gid] = sids
        return {"sid": gid, "token": toks}

    def _group_op(self, op: str, gid: str) -> Dict[str, Any]:
        with self._lock:
            sids = self._groups.get(gid)
        if sids is None:
            return {"error": f"unknown session {gid!r} (ended, "
                             f"evicted, or never started)"}
        if op == "end":
            for s in sids:
                self._engine.end(s)
            with self._lock:
                self._groups.pop(gid, None)
            return {"ended": True}
        # op in ("next", "next_chunk"): one decode step for every
        # member (rows share a prompt length, so they reach the cache
        # cap together)
        toks = []
        for s in sids:
            out = self._engine.next_chunk(s, 1)
            if "error" in out:
                return out
            if not out["tokens"]:
                return {"error": f"session {gid!r} finished "
                                 f"(cache capacity reached)"}
            toks.extend(out["tokens"])
        if op == "next_chunk":
            return {"tokens": toks, "done": False}
        return {"token": toks}
