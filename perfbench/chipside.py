"""What runs in the one process that holds the chip, whichever traffic kind
started it: the worker's report of itself, the count of compilations, and
the profiler switch.  Imports JAX, so the runner never imports this module."""

from __future__ import annotations

import os
import time
from typing import Any, Dict, Optional

import jax


def configure_jax() -> None:
    """Every program of the cell goes to the persistent cache, the small
    ones too: a program under JAX's default one-second threshold would
    compile again in every run and set-up would not repeat."""
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    _CompileCounter.install()


class _CompileCounter:
    """Programs compiled or loaded from the persistent cache by this
    process, by JAX's own monitoring events."""

    count = 0
    _installed = False

    @classmethod
    def install(cls) -> None:
        if cls._installed:
            return
        cls._installed = True
        from jax import monitoring

        def on_duration(event: str, _secs: float, **_kw) -> None:
            if event == "/jax/core/compile/backend_compile_duration":
                cls.count += 1

        monitoring.register_event_duration_secs_listener(on_duration)


def compiles() -> int:
    return _CompileCounter.count


def program_bytes(compiled) -> int:
    """What one device holds while ``compiled`` runs, by the compiler's own
    account: arguments, temporaries and the outputs that alias no argument.
    The TPU allocator's ``peak_bytes_in_use`` leaves a program's temporaries
    out (the train step: 4.29 GB read against 4.26 GB of arguments plus
    6.97 GB of temporaries declared), so `report` takes the larger."""
    m = compiled.memory_analysis()
    return int(m.argument_size_in_bytes + m.temp_size_in_bytes
               + m.output_size_in_bytes - m.alias_size_in_bytes)


def report(program_peak: int = 0) -> Dict[str, Any]:
    """Who this process is and what JAX gave it."""
    d = jax.devices()
    peak = program_peak
    for dev in d:
        stats = dev.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"pid": os.getpid(),
            "jax_platforms": os.environ.get("JAX_PLATFORMS"),
            "compile_cache_dir": jax.config.jax_compilation_cache_dir,
            "device": {"platform": d[0].platform, "kind": d[0].device_kind,
                       "count": len(d), "memory_peak_bytes": peak},
            "compiles": compiles()}


class Tracer:
    """jax.profiler around part of the window.  The trace goes to a fixed
    directory inside the checkout, emptied first."""

    def __init__(self, out_dir: Optional[str]):
        self.dir = out_dir
        self.t_start = self.t_stop = None

    def start(self) -> None:
        if not self.dir or self.t_start is not None:
            return
        import shutil
        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0      # host spans come from
        opts.host_tracer_level = 2        # TraceAnnotation alone
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.t_start = time.time()

    def stop(self) -> None:
        if self.t_start is None or self.t_stop is not None:
            return
        self.t_stop = time.time()
        jax.profiler.stop_trace()

    def result(self) -> Optional[Dict[str, Any]]:
        if self.t_stop is None:
            return None
        return {"dir": self.dir, "t_start": self.t_start,
                "t_stop": self.t_stop}


def annotate(name: str):
    """A host span in the profiler's own trace (same clock as the device
    lines); costs nothing while no trace is being taken."""
    return jax.profiler.TraceAnnotation(name)
