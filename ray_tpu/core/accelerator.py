"""Which process of a node may open the chip, and where compiled programs go.

A TPU chip belongs to one process at a time, and stock JAX registers the
TPU backend ``fail_quietly``: with ``JAX_PLATFORMS`` unset, a process that
cannot open the chip (another process holds it) logs at INFO and computes
on the CPU.  This runtime is many processes, so the rule is made here, in
one place, and ``JAX_PLATFORMS`` is the only switch:

* work that holds a ``TPU`` reservation runs in a worker whose
  ``JAX_PLATFORMS`` is ``tpu`` before JAX is imported, so a failed claim
  raises instead of falling back;
* every other process (driver, controller, nodelet, zygote, dashboard
  agent, workers without a reservation) is pinned to ``cpu`` and can never
  take the chip from the worker that reserved it;
* a node started with ``JAX_PLATFORMS=cpu`` is a CPU node throughout: no
  detection, and a ``TPU`` resource given by hand is a scheduling token
  only (the test suite's mode).

Nothing in this module imports JAX, except `open_reserved_chip` when it is
called in a worker on the TPU platform.
"""

from __future__ import annotations

import glob
import json
import os
import subprocess
import sys
from typing import Dict, Mapping, Optional

CPU = "cpu"
TPU = "tpu"


def expects_tpu(env: Mapping[str, str]) -> Optional[bool]:
    """True: ``JAX_PLATFORMS`` names the TPU, so a node that finds none is
    broken.  False: it names something else (``cpu``).  None: unset —
    detection decides."""
    platforms = env.get("JAX_PLATFORMS", "")
    if not platforms:
        return None
    return TPU in platforms.split(",")


def reserved_platform(env: Mapping[str, str]) -> str:
    """The platform this node gives to work that reserved ``TPU``."""
    return CPU if expects_tpu(env) is False else TPU


def worker_platform(resources: Mapping[str, float],
                    reserved: str = TPU) -> str:
    """The one platform a worker leased for ``resources`` may initialise:
    ``reserved`` (the TPU, on a node that has one) for a ``TPU``
    reservation, the CPU for everything else.  Inside a placement group
    the reservation arrives under the bundle's shadow names
    (``TPU_group_<index>_<pg>``, ``TPU_group_<pg>``)."""
    holds_tpu = any(amount > 0 and (name == "TPU"
                                    or name.startswith("TPU_group_"))
                    for name, amount in resources.items())
    return reserved if holds_tpu else CPU


def pin_to_cpu() -> Optional[str]:
    """Hold this process (and the children that inherit its environment)
    to the CPU backend.  Returns what ``JAX_PLATFORMS`` held before
    (None: unset): a launcher that goes on to start a node hands it that
    value, not the pin (`node.start_nodelet`'s ``env``)."""
    before = os.environ.get("JAX_PLATFORMS")
    os.environ["JAX_PLATFORMS"] = CPU
    jax = sys.modules.get("jax")
    if jax is not None:
        jax.config.update("jax_platforms", CPU)
    return before


_chip_opened = False


def open_reserved_chip() -> None:
    """In a worker started on the TPU platform (it holds a ``TPU``
    reservation), initialise the TPU backend now, as the ring span
    ``setup:chip_open``.  Called by the runtime's own entry points just
    before they hand over to code that will use the chip (a Serve
    replica's constructor, the train backend's worker set-up), so the
    opening is timed apart from the user's first program.  Nothing
    happens in a process on any other platform, nor a second time.  A
    backend that cannot be opened raises here what the user's first JAX
    call would have raised."""
    global _chip_opened
    if _chip_opened or os.environ.get("JAX_PLATFORMS") != TPU:
        return
    _chip_opened = True
    from ..util import tracing
    with tracing.span("setup:chip_open", "setup", worker_pid=os.getpid()):
        import jax
        jax.devices()


# ---------------------------------------------------------------- detection

_PROBE = ("import jax, json; d = jax.devices(); "
          "print('TPUPROBE ' + json.dumps({'platform': d[0].platform, "
          "'n': len(d), 'kind': d[0].device_kind}))")


def _chip_device_files() -> list:
    return glob.glob("/dev/accel*") + glob.glob("/dev/vfio/[0-9]*")


def detect_tpu_resources(env: Mapping[str, str], *,
                         timeout_s: float) -> Dict[str, float]:
    """``{"TPU": n, "accelerator_type:<kind>": 1}`` for the chips of this
    host, ``{}`` for a host without any.

    The probe is a child process that opens the chip and has exited —
    and so let go of it — before this returns, which is before the
    nodelet grants its first lease.  A probe that fails or times out on a
    node that should have a chip raises: starting that node "without TPU"
    would run its accelerator work on the CPU in silence."""
    expected = expects_tpu(env)
    if expected is False:
        return {}
    probe_env = dict(env)
    if expected:
        probe_env["JAX_PLATFORMS"] = TPU
    try:
        out = subprocess.run([sys.executable, "-c", _PROBE], env=probe_env,
                             capture_output=True, text=True,
                             timeout=timeout_s)
    except subprocess.TimeoutExpired:
        raise RuntimeError(
            f"TPU probe did not answer within {timeout_s}s") from None
    info = None
    for line in out.stdout.splitlines():
        if line.startswith("TPUPROBE "):
            info = json.loads(line[len("TPUPROBE "):])
    if info is None or info["platform"] != TPU:
        if expected or _chip_device_files():
            raise RuntimeError(
                "this node should have a TPU and the probe did not get one "
                f"(exit code {out.returncode}, saw "
                f"{info['platform'] if info else 'nothing'}; another "
                f"process may hold the chip): {out.stderr.strip()[-600:]}")
        return {}
    kind = str(info["kind"]).replace(" ", "-")
    return {"TPU": float(info["n"]), f"accelerator_type:{kind}": 1.0}


# ------------------------------------------------------------ compile cache

def place_compile_cache() -> None:
    """Decide where this process keeps compiled programs; run before its
    first compile (package import, and again in a forked worker once its
    environment is final).

    Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and no
    other directory is set here.  Where it is not, a process that may use
    the chip gets ``<checkout>/.jax_compile_cache`` — a fixed path, since
    the path is part of the cache key.  Processes pinned to the CPU get no
    default: the CPU suite's compiles are short, and a described-topology
    compile written there cannot be read back without a chip.

    Whichever directory it is, an entry's key holds the program's metadata
    (``jax_compilation_cache_include_metadata_in_key``).  JAX strips it by
    default, and a program whose instructions did not change is then a HIT
    on an executable compiled before an edit to its `jax.named_scope`
    names: its text carries the OLD ``op_name`` paths, and the compile
    ledger's op map (`util/device_profile.py`) places a trace's ops by
    them.  The price: an edit that moves a line on a program's call path
    compiles that program once more (as the programs with a Pallas kernel
    always did: a kernel's body holds its source locations)."""
    jax = sys.modules.get("jax")
    if "JAX_COMPILATION_CACHE_INCLUDE_METADATA_IN_KEY" not in os.environ:
        os.environ["JAX_COMPILATION_CACHE_INCLUDE_METADATA_IN_KEY"] = "true"
        if jax is not None:
            jax.config.update(
                "jax_compilation_cache_include_metadata_in_key", True)
    if os.environ.get("JAX_COMPILATION_CACHE_DIR") \
            or os.environ.get("JAX_PLATFORMS") == CPU:
        return
    checkout = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    path = os.path.join(checkout, ".jax_compile_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    if jax is not None and not jax.config.jax_compilation_cache_dir:
        jax.config.update("jax_compilation_cache_dir", path)
