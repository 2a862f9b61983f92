"""Per-dispatch device profiling: the data-plane flight instruments.

PR-10's flight recorder made the control plane explainable after the
fact; this module does the same for the DATA plane.  Every registered
jitted program (decode step, prefill chunk, cache insert/gather) is
wrapped ONCE in a timing shim that records, per program:

* dispatch count and cumulative dispatch wall time (always);
* device time, from the samples the program's CALLER hands in
  (:meth:`DispatchProfiler.note_device_seconds`): the shim itself never
  waits for the device on a shape it has seen, because a loop that keeps
  a step in flight (the decode engine) would be emptied by every such
  wait.  The engine samples where it reads a step's tokens anyway; the
  estimate extrapolates the sampled mean over all dispatches;
* the argument-shape key of each dispatch, and the wall time of every
  FIRST-SEEN shape (the one dispatch the shim does wait out: it
  compiles) — the **compile ledger**.  A novel shape means XLA
  traces + compiles inside that dispatch, so its wall time is the
  observed compile cost and the recompile count is exactly the distinct
  shape count.  A ledger growing with traffic instead of staying O(1)
  is a compile storm — counted here, alerted via the nodelet's
  ``compile_storm`` flight-recorder trigger;
* tokens processed (host-known counts fed by the engine via
  :meth:`DispatchProfiler.note_tokens` — no device sync) and an
  analytic FLOPs-per-token figure (``models.decode_flops_per_token``),
  giving a roofline/MFU estimate per program:
  ``mfu = tokens * flops_per_token / device_seconds / peak_flops``;
* each compiled program's **op map**: what a profiler trace needs to place
  the program's device ops in the model.  A trace names an op by the
  compiler's numbering (``fusion.401``) and by nothing else; the optimised
  HLO text of the executable names the same instructions and carries, for
  each, the ``op_name`` path JAX gave it
  (``jit(step)/transpose(jvp())/while/body/checkpoint/attention/mul``), in
  which the model programs' `jax.named_scope` names stand
  (`MODEL_PARTS`).  `watch` registers a program by the name of its HLO
  module; when JAX loads an executable of that name (compiled, or taken
  from the persistent cache: a first-seen shape either way), `op_map`
  reads the text off THAT executable and the map goes to
  ``<session_dir>/programs/<kind>-<pid>.<program>.json`` with the
  process's span file (`util/tracing.py`), one ring span
  ``program:compiled`` marking it.  The TEXT is taken once a program and
  shape, inside the compile that the first dispatch pays anyway (tens of
  milliseconds), never on a later dispatch; the map is made from it where
  the process writes its files or `tracing.program_maps` is asked (0.1-0.2
  s for a program of a thousand instructions: not a warm-up's to pay).

The train step is in the ledger by `watch` alone
(`models.make_train_step`): no shim at all.

The wrap is idempotent: wrapping an already-wrapped callable re-wraps
the ORIGINAL underneath, never stacking shims — critical because the
prefill chunk program is a module-level shared jit and every engine
(re)start wraps it again; stacking would double-count every dispatch.

Snapshots are cumulative plain dicts; the serve engine ships them on
its existing ``serve_metrics`` push and the nodelet folds deltas into
``ray_tpu_device_{dispatches,device_seconds,compile_seconds,compiles}``
counters and the ``ray_tpu_mfu_ratio`` gauge.

MFU needs a peak: ``device_profile_peak_flops`` when set, else the
published figure for the chip's exact ``device_kind``.  A TPU the table
does not name is an error, and on any other backend there is no peak and
so no ``mfu``: a CPU run never publishes one.
"""

from __future__ import annotations

import collections
import functools
import hashlib
import re
import threading
import time
from typing import (Any, Callable, Dict, List, NamedTuple, Optional,
                    Tuple)

# bf16 peak TFLOP/s and HBM GB/s per chip, keyed by the exact
# `device_kind` JAX reports (Google Cloud TPU documentation,
# per-generation system architecture pages; a v5e reports itself as
# "TPU v5 lite").
PEAK_TFLOPS = {
    "TPU v4": 275.0,
    "TPU v5 lite": 197.0,
    "TPU v5e": 197.0,
    "TPU v5p": 459.0,
    "TPU v6 lite": 918.0,
    "TPU v6e": 918.0,
}
PEAK_HBM_GBPS = {
    "TPU v4": 1200.0,
    "TPU v5 lite": 819.0,
    "TPU v5e": 819.0,
    "TPU v5p": 2765.0,
    "TPU v6 lite": 1640.0,
    "TPU v6e": 1640.0,
}


def peak_flops_of(device_kind: str) -> float:
    """Published peak FLOP/s of one chip; an unknown kind is an error,
    never a default."""
    if device_kind not in PEAK_TFLOPS:
        raise KeyError(
            f"no published peak for device kind {device_kind!r} "
            f"(known: {sorted(PEAK_TFLOPS)})")
    return PEAK_TFLOPS[device_kind] * 1e12


def _tpu_kind() -> Optional[str]:
    """`device_kind` of the attached TPU; None on any other backend."""
    import jax
    dev = jax.devices()[0]
    return dev.device_kind if dev.platform == "tpu" else None


def peak_flops() -> Optional[float]:
    """Per-device peak FLOP/s: the config override, else the table entry
    of the attached TPU; None on a backend that has no published peak."""
    from ..core.config import GlobalConfig
    cfg = getattr(GlobalConfig, "device_profile_peak_flops", 0.0) or 0.0
    if cfg > 0:
        return float(cfg)
    kind = _tpu_kind()
    return None if kind is None else peak_flops_of(kind)


def ridge_rows(weight_itemsize: float,
               device_kind: Optional[str] = None) -> Optional[float]:
    """Rows at which a matmul against weights of ``weight_itemsize``
    bytes an element stops being a read of those weights: ``2 * rows``
    FLOP an element against ``itemsize`` bytes, so ``peak FLOP/s *
    itemsize / (2 * HBM bytes/s)``.  Below it a program costs one read
    of the weights it touches whatever its rows; above it time grows
    with them.  The table entry of ``device_kind`` (default: the
    attached TPU's; an unnamed TPU is an error); None on a backend that
    has no published peaks."""
    kind = device_kind if device_kind is not None else _tpu_kind()
    if kind is None:
        return None
    return peak_flops_of(kind) * weight_itemsize \
        / (2.0 * PEAK_HBM_GBPS[kind] * 1e9)


def _shape_key(args: tuple, kwargs: dict) -> tuple:
    """Cheap per-dispatch shape fingerprint: the shapes of TOP-LEVEL
    array arguments plus scalar statics.  Pytrees (params, caches) are
    summarized as ``*`` — walking them per dispatch would cost more
    than the dispatch; the dims that actually vary (token blocks,
    chunk widths, static ints) are all top-level here."""
    key: List[Any] = []
    for a in args:
        shape = getattr(a, "shape", None)
        if shape is not None:
            key.append(tuple(int(d) for d in shape))
        elif isinstance(a, (int, bool, str, float)):
            key.append(a)
        else:
            key.append("*")
    for k in sorted(kwargs):
        v = kwargs[k]
        key.append((k, getattr(v, "shape", None) or
                    (v if isinstance(v, (int, bool, str, float))
                     else "*")))
    return tuple(key)


class _ProgramStats:
    """Cumulative ledger of one wrapped program (single writer — the
    dispatching thread; snapshot readers tolerate torn reads)."""

    __slots__ = ("program", "dispatches", "wall_s", "sampled_s",
                 "sampled_n", "compile_s", "compiles", "shapes",
                 "tokens", "flops_per_token")

    def __init__(self, program: str):
        self.program = program
        self.dispatches = 0
        self.wall_s = 0.0
        self.sampled_s = 0.0        # device seconds the caller sampled
        self.sampled_n = 0          # ... over this many dispatches
        self.compile_s = 0.0        # wall time of first-seen shapes
        self.compiles = 0           # distinct argument-shape keys seen
        self.shapes: set = set()
        self.tokens = 0
        self.flops_per_token = 0.0

    def device_seconds(self) -> float:
        """Extrapolated device time: sampled mean × all dispatches.
        Without a sample (a program whose caller hands none in),
        dispatch wall time is the bound (async dispatch makes it an
        underestimate, never zero)."""
        if self.sampled_n:
            return self.sampled_s * (self.dispatches
                                     / max(1, self.sampled_n))
        return self.wall_s

    def mfu(self, peak: Optional[float]) -> Optional[float]:
        """None without a device-time sample: a share of the peak over
        dispatch walls would read far past it."""
        dev = self.device_seconds()
        if not self.flops_per_token or not self.tokens or dev <= 0 \
                or not self.sampled_n or not peak or peak <= 0:
            return None
        return (self.tokens * self.flops_per_token) / dev / peak


class DispatchProfiler:
    """Wrap-once timing shims over a set of named jitted programs."""

    def __init__(self):
        self._stats: Dict[str, _ProgramStats] = {}
        self._lock = threading.Lock()

    # ------------------------------------------------------------ wiring
    def _stat(self, program: str) -> _ProgramStats:
        st = self._stats.get(program)
        if st is None:
            with self._lock:
                st = self._stats.setdefault(program,
                                            _ProgramStats(program))
        return st

    def wrap(self, program: str, fn: Callable) -> Callable:
        """Return ``fn`` timed under ``program``.  Idempotent: a
        callable that is already a profiler shim (this profiler's or a
        previous engine incarnation's) is unwrapped to the original
        first, so re-registration after an engine restart never stacks
        two timers over one dispatch."""
        inner = getattr(fn, "_rt_profiled_inner", None)
        if inner is not None:
            fn = inner
        st = self._stat(program)
        watch(program, fn)

        def dispatch(*args, **kwargs):
            key = _shape_key(args, kwargs)
            novel = key not in st.shapes
            t0 = time.perf_counter()
            out = fn(*args, **kwargs)
            if novel:
                try:
                    import jax
                    out = jax.block_until_ready(out)
                except Exception:
                    pass
            dt = time.perf_counter() - t0
            st.dispatches += 1
            st.wall_s += dt
            if novel:
                # first dispatch of a shape pays trace + compile: its
                # wall time IS the observed compile cost
                st.shapes.add(key)
                st.compiles += 1
                st.compile_s += dt
            return out

        dispatch._rt_profiled_inner = fn
        dispatch._rt_profiler = self
        dispatch.__name__ = getattr(fn, "__name__", program)
        return dispatch

    # ---------------------------------------------------------- feeding
    def note_tokens(self, program: str, n: int) -> None:
        """Credit ``n`` processed tokens to ``program`` — host-known
        counts (batch occupancy, chunk width) so the MFU numerator
        never costs a device sync."""
        if n > 0:
            self._stat(program).tokens += n

    def note_device_seconds(self, program: str, seconds: float) -> None:
        """One dispatch of ``program`` took the device ``seconds``: a
        sample from the caller, taken where it waits for the program's
        output anyway (the engine: between the returns of two
        consecutive steps' reads)."""
        st = self._stat(program)
        st.sampled_s += seconds
        st.sampled_n += 1

    def set_flops_per_token(self, program: str, flops: float) -> None:
        self._stat(program).flops_per_token = float(flops or 0.0)

    # --------------------------------------------------------- snapshot
    def wall_seconds(self) -> Dict[str, float]:
        """program -> cumulative dispatch wall seconds (the phase-
        attribution source: wall, not sampled device time, because the
        engine thread is occupied for the whole dispatch)."""
        with self._lock:
            return {p: s.wall_s for p, s in self._stats.items()}

    def wall_of(self, program: str) -> float:
        """One program's cumulative dispatch wall seconds, lock-free:
        for the thread that dispatches it (the engine reads it around a
        dispatch to split that program's wall by kind of call)."""
        return self._stat(program).wall_s

    def distinct_shapes(self) -> int:
        with self._lock:
            return sum(len(s.shapes) for s in self._stats.values())

    def total_compiles(self) -> int:
        with self._lock:
            return sum(s.compiles for s in self._stats.values())

    def snapshot(self, peak: Optional[float] = None) -> List[dict]:
        """Cumulative per-program rows, wire-ready for the nodelet fold
        (every numeric travels cumulative; the nodelet incs deltas)."""
        pk = peak if peak is not None else peak_flops()
        rows = []
        with self._lock:
            stats = list(self._stats.values())
        for st in sorted(stats, key=lambda s: s.program):
            mfu = st.mfu(pk)
            rows.append({
                "program": st.program,
                "dispatches": st.dispatches,
                "wall_s": round(st.wall_s, 6),
                "device_s": round(st.device_seconds(), 6),
                "compile_s": round(st.compile_s, 6),
                "compiles": st.compiles,
                "shapes": len(st.shapes),
                "tokens": st.tokens,
                "mfu": None if mfu is None else round(mfu, 6),
            })
        return rows


# ---------------------------------------------------------------- op maps

#: the `jax.named_scope` names in the model programs (`models/`, `ops/`,
#: the engine's fused step): the part of the model an instruction belongs
#: to.  Lower case, one word; a reader of a trace knows this set.
MODEL_PARTS = ("embed", "norm", "projections", "attention", "cache_write",
               "ffn", "experts", "conv", "head", "optimizer")
# A scope may stand INSIDE a part, for a reader that wants it apart (the part
# counts it too: the parts still add up): ``summary``, a summary layer's
# pooling of a chunk and the write of its row (`models/generate.py`
# `_write_summaries`), inside ``cache_write``; ``indexer``, what learned
# sparse attention adds to a layer (`ops/sparse_index.py`: index projections,
# scores, the choice), inside ``attention``; ``kda``, what a gated delta rule
# adds to a layer beside its projections (`models/transformer.py`
# `kda_operator`, `ops/delta_rule.py`), inside ``attention`` too, but for its
# convolutions, which keep ``conv``.  Or AROUND parts: ``ssm``, all of a
# state-space mixer (`transformer.ssm_operator`), and ``window_latent``, all
# of a window layer's operator over a latent cache
# (`transformer.latent_scope`), whose projections, ring write and attention
# keep their parts' names inside it.  Not parts of their own while
# the benchmark's list (`perfbench/parts.py` ``PARTS``, held equal to
# `MODEL_PARTS` by its tests) has ten.

# opcodes that are no work of their own: never an event of a trace
_FREE_OPS = frozenset(("parameter", "constant", "get-tuple-element", "tuple",
                       "bitcast", "after-all", "partition-id", "replica-id"))
_INSTRUCTION = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_OPCODE = re.compile(r"\s*([\w\-]+)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLED = re.compile(r"\b(calls|body|condition|to_apply|true_computation|"
                     r"false_computation)=%?([\w.\-]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")
_OPERAND = re.compile(r"%([\w.\-]+)")
_INDEX = re.compile(r"\bindex=(\d+)")
# a value of these is a tuple or a whole computation's: what consumes it
# says nothing of one operand, and a name goes through a loop only from
# the body's element to the operand that feeds it (`_inherit`)
_OPAQUE = frozenset(("tuple", "get-tuple-element", "while", "conditional",
                     "call", "parameter"))
# a path component that may be a scope: a word, bare or inside transforms
# (not ``jit(step)``: that names a function)
_SCOPE = re.compile(r"^(?:(?:jvp|transpose|vmap)\()*(\w*)\)*$")
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class _Ins(NamedTuple):
    """One instruction of a compiled program's text."""
    name: str
    opcode: str
    op_name: str                # its metadata's path, "" where it has none
    called: Dict[str, str]      # attribute (``body``, ``calls``) -> callee
    operands: List[str]
    index: Optional[int]        # a ``get-tuple-element``'s


def part_of(op_name: str) -> Tuple[Optional[str], str]:
    """An instruction's ``op_name`` path -> (part, direction).  The part
    is the LAST component that is one of `MODEL_PARTS` (the innermost
    scope wins), bare or inside the transformations JAX wrapped it in
    (``transpose(jvp(norm))``); None where the path holds none.  The
    direction is ``recompute`` under a ``rematted_computation`` component
    (a forward that `jax.checkpoint` runs again in the backward pass), else
    ``backward`` with ``transpose(`` anywhere in the path, else
    ``forward``."""
    steps = op_name.split("/")
    part = next((m.group(1) for m in map(_SCOPE.match, reversed(steps))
                 if m and m.group(1) in MODEL_PARTS), None)
    if "rematted_computation" in steps:
        return part, "recompute"
    return part, "backward" if "transpose(" in op_name else "forward"


def _opcode(rest: str) -> str:
    """The opcode of an instruction's right-hand side: what follows its
    shape (one word, or a parenthesised tuple of shapes)."""
    end = rest.find(" ")
    if rest.startswith("("):
        depth = 0
        for end, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if not depth:
                break
        end += 1
    m = _OPCODE.match(rest, end)
    return m.group(1) if m else ""


def _fusion_op_name(inner: List[_Ins], own: str) -> str:
    """The ``op_name`` a fusion is placed by.  Its own metadata is its
    root's, and the root of an output fusion is what consumed the matmul
    (the residual add, a tuple): so a fusion goes by its matmul where it
    has one, else by the part most of its instructions carry (the later
    on a tie), and keeps its own where none of them carries any."""
    placed = [(i.opcode, part_of(i.op_name)[0], i.op_name) for i in inner]
    for op, part, name in reversed(placed):
        if part and op in ("dot", "convolution"):
            return name
    counts = collections.Counter(part for _, part, _ in placed if part)
    if not counts:
        return own
    most = max(counts.values())
    return next(name for _, part, name in reversed(placed)
                if part and counts[part] == most)


def op_map(hlo_text: str) -> Dict[str, Any]:
    """The optimised HLO text of one executable -> ``{"module": the HLO
    module's name (what a trace's ``XLA Modules`` line shows), "shape": a
    digest of the module's header (its arguments' and results' shapes and
    layouts: what tells two compiled shapes of one program apart),
    "instructions": {instruction name: op_name}, "named": how many of
    them lie in a part}``.  The instructions are those a trace's ``XLA
    Ops`` line can show: the entry computation's and those of every
    ``while`` body and condition, conditional branch and called
    computation it reaches, never the inside of a fusion.  A fusion is
    placed by `_fusion_op_name`, a call without metadata by its root, and
    an instruction the COMPILER made (no ``op_name`` path: a layout copy
    of a weight, an asynchronous copy's start and end, a partitioner's
    all-reduce) by the first placed instruction its value reaches
    (`_inherit`): the copy is that part's cost."""
    module, shape, entry = "", "", None
    comps: Dict[str, List[_Ins]] = {}
    root_name: Dict[str, str] = {}         # computation -> its root's op_name
    cur = None                             # the computation being read
    for line in hlo_text.splitlines():
        if not line:
            continue
        if not line[0].isspace():
            if line.startswith("HloModule "):
                module = line.split()[1].rstrip(",")
                shape = hashlib.md5(line.encode()).hexdigest()[:12]
            elif line.rstrip().endswith("{"):
                head = line.split()
                name = head[1] if head[0] == "ENTRY" else head[0]
                cur = name.lstrip("%")
                comps[cur] = []
                if head[0] == "ENTRY":
                    entry = cur
            continue
        m = _INSTRUCTION.match(line)
        if m is None or cur is None:
            continue
        name, rest = m.groups()
        called = dict(_CALLED.findall(rest))
        b = _BRANCHES.search(rest)
        if b:
            called.update((f"branch{i}", c.strip().lstrip("%"))
                          for i, c in enumerate(b.group(1).split(",")))
        n = _OP_NAME.search(rest)
        own = n.group(1) if n else ""
        index = _INDEX.search(rest)
        comps[cur].append(_Ins(
            name, _opcode(rest), own, called,
            _OPERAND.findall(rest.split(", metadata=")[0]),
            int(index.group(1)) if index else None))
        if line.lstrip().startswith("ROOT "):
            root_name[cur] = own
    out: Dict[str, str] = {}
    todo, seen = [entry], set()
    while todo:
        comp = todo.pop()
        if comp in seen or comp not in comps:
            continue
        seen.add(comp)
        for name, op, own, called, _, _ in comps[comp]:
            if op in _FREE_OPS:
                continue
            if op == "fusion":
                own = _fusion_op_name(comps.get(called.get("calls"), []),
                                      own)
            else:
                # a reduce's or a collective's ``to_apply`` is a scalar
                # function, no event; every other callee's instructions
                # are on the line themselves
                inner = [c for k, c in called.items()
                         if not (k == "to_apply" and op != "call")]
                todo += inner
                if not own and inner:
                    own = root_name.get(inner[0], "")
            out[name] = own
    _inherit(comps, seen, out)
    return {"module": module, "shape": shape, "instructions": out,
            "named": sum(1 for v in out.values() if part_of(v)[0])}


def _inherit(comps: Dict[str, List[_Ins]], seen: set,
             out: Dict[str, str]) -> None:
    """Give every instruction the COMPILER made (no ``op_name`` path of its
    own) the path of the first placed instruction its value reaches: through
    other such instructions, through the program's own unscoped ones (a
    scan's slice of the stacked weights: they carry a name on and keep
    theirs), and through a loop from the body's tuple element back to the
    operand that feeds it (a layout copy of a weight stack in front of the
    layer loop is the cost of the part that multiplies by it)."""
    flow = {k: v for k, v in out.items() if part_of(v)[0]}
    opcode = {i.name: i.opcode for c in seen for i in comps[c]}
    elements: Dict[str, Dict[int, List[str]]] = {}     # body -> index -> gtes
    for c in seen:
        for i in comps[c]:
            if i.opcode == "get-tuple-element" and i.index is not None:
                elements.setdefault(c, {}).setdefault(
                    i.index, []).append(i.name)
    operands_of = {i.name: i.operands for c in seen for i in comps[c]}

    def give(to: str, path: str) -> bool:
        if to in flow or opcode.get(to) in _OPAQUE - {"get-tuple-element"}:
            return False
        flow[to] = path
        return True

    for _ in range(10):     # a chain's length: copy, bitcast, loop, slice
        moved = False
        for c in seen:
            for name, op, _, called, operands, _ in comps[c]:
                if op == "while" and operands:
                    fed = operands_of.get(operands[0], [])
                    mine = elements.get(called.get("body"), {})
                    for i, o in enumerate(fed):
                        path = next((flow[g] for g in mine.get(i, [])
                                     if g in flow), None)
                        if path and opcode.get(operands[0]) == "tuple":
                            moved |= give(o, path)
                elif name in flow and (op not in _OPAQUE
                                       or op == "get-tuple-element"):
                    for o in operands:
                        if o in opcode:
                            moved |= give(o, flow[name])
        if not moved:
            break
    for name, path in flow.items():
        if "/" not in out.get(name, "/"):
            out[name] = path


_watch_lock = threading.Lock()
_watched: Dict[str, str] = {}      # compile event's fun_name -> program
_listening = False


def watch(program: str, fn: Callable) -> None:
    """Put the jitted ``fn`` in the compile ledger as ``program``: every
    executable JAX loads for it from now on (one a first-seen argument
    shape) leaves its `op_map` with the process's spans.  Costs nothing
    on a dispatch: the executable's text is taken in the compile, by JAX's
    own compile event, and the map made when the files are.  Called by
    `DispatchProfiler.wrap`, and directly for a program that must never be
    sampled (the train step)."""
    global _listening
    name = getattr(fn, "__name__", "")
    if not name:
        return
    with _watch_lock:
        _watched[f"jit({name})"] = program
        if _listening:
            return
        _listening = True
    from jax import monitoring
    monitoring.register_event_duration_secs_listener(_on_compile)


def _on_compile(event: str, _secs: float, fun_name: str = "",
                **_kw: Any) -> None:
    """JAX has compiled ``fun_name`` or taken it from the persistent cache
    (the event covers both) and holds the executable: the newest live one
    whose HLO module has that name.  Never raises: it runs inside a
    compile."""
    program = _watched.get(fun_name) if event == _COMPILE_EVENT else None
    if program is None:
        return
    t0 = time.time()
    try:
        import jax

        from . import tracing
        module = "jit_" + fun_name[4:-1]
        for exe in jax.devices()[0].client.live_executables()[:4]:
            hlo = exe.hlo_modules()[0]
            if hlo.name != module:
                continue
            text = hlo.to_string()
            tracing.record_program(program, functools.partial(op_map, text),
                                   t0, time.time() - t0)
            return
    except Exception:
        pass
