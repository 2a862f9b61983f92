"""Per-dispatch device profiling: the data-plane flight instruments.

PR-10's flight recorder made the control plane explainable after the
fact; this module does the same for the DATA plane.  Every registered
jitted program (decode step, prefill chunk, cache insert/gather,
draft/verify, train step) is wrapped ONCE in a timing shim that records,
per program:

* dispatch count and cumulative dispatch wall time (always);
* block-until-ready device time, sampled every Nth dispatch
  (``device_profile_sample_every``) so the hot loop stays hot — the
  estimate extrapolates the sampled mean over all dispatches;
* the argument-shape key of each dispatch, and the wall time of every
  FIRST-SEEN shape — the **compile ledger**.  A novel shape means XLA
  traces + compiles inside that dispatch, so its wall time is the
  observed compile cost and the recompile count is exactly the distinct
  shape count.  A ledger growing with traffic instead of staying O(1)
  is a compile storm — counted here, alerted via the nodelet's
  ``compile_storm`` flight-recorder trigger;
* tokens processed (host-known counts fed by the engine via
  :meth:`DispatchProfiler.note_tokens` — no device sync) and an
  analytic FLOPs-per-token figure (``models.decode_flops_per_token``),
  giving a roofline/MFU estimate per program:
  ``mfu = tokens * flops_per_token / device_seconds / peak_flops``.

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

import threading
import time
from typing import Any, Callable, Dict, List, Optional

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
        self.sampled_s = 0.0        # block-until-ready sample total
        self.sampled_n = 0          # dispatches actually sampled
        self.compile_s = 0.0        # wall time of first-seen shapes
        self.compiles = 0           # distinct argument-shape keys seen
        self.shapes: set = set()
        self.tokens = 0
        self.flops_per_token = 0.0

    def device_seconds(self) -> float:
        """Extrapolated device time: sampled mean × all dispatches.
        Until the first sample lands, dispatch wall time is the bound
        (async dispatch makes it an underestimate, never zero)."""
        if self.sampled_n:
            return self.sampled_s * (self.dispatches
                                     / max(1, self.sampled_n))
        return self.wall_s

    def mfu(self, peak: Optional[float]) -> Optional[float]:
        dev = self.device_seconds()
        if not self.flops_per_token or not self.tokens or dev <= 0 \
                or not peak or peak <= 0:
            return None
        return (self.tokens * self.flops_per_token) / dev / peak


class DispatchProfiler:
    """Wrap-once timing shims over a set of named jitted programs."""

    def __init__(self, sample_every: Optional[int] = None):
        self._sample_every = sample_every
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

    def _every(self) -> int:
        if self._sample_every is not None:
            return max(1, int(self._sample_every))
        from ..core.config import GlobalConfig
        return max(1, int(getattr(GlobalConfig,
                                  "device_profile_sample_every", 10)))

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

        def dispatch(*args, **kwargs):
            key = _shape_key(args, kwargs)
            novel = key not in st.shapes
            sample = novel or (st.dispatches + 1) % self._every() == 0
            t0 = time.perf_counter()
            out = fn(*args, **kwargs)
            if sample:
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
                # wall time IS the observed compile cost (excluded from
                # the device-time sample pool so MFU is steady-state)
                st.shapes.add(key)
                st.compiles += 1
                st.compile_s += dt
            elif sample:
                st.sampled_s += dt
                st.sampled_n += 1
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
