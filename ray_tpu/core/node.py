"""Process bootstrap: spawn controller/nodelet daemons for a local cluster.

Equivalent of the reference's Node + services.py process orchestration
(/root/reference/python/ray/_private/node.py:41, services.py:1200,1273):
daemons are separate OS processes whose ready lines are read from stdout.
"""

from __future__ import annotations

import atexit
import os
import signal
import subprocess
import sys
import tempfile
import time
import uuid
from typing import Dict, Optional

from . import accelerator
from .config import GlobalConfig


def sessions_base() -> str:
    # NB: not <tmp>/ray_tpu — a directory named like the package next to a
    # user's script would shadow the real package on sys.path.
    return os.environ.get("RAY_TPU_TMPDIR") or os.path.join(
        tempfile.gettempdir(), "ray-tpu-sessions")


def new_session_dir() -> str:
    base = sessions_base()
    path = os.path.join(base, f"session_{time.strftime('%Y%m%d-%H%M%S')}_{uuid.uuid4().hex[:8]}")
    os.makedirs(os.path.join(path, "logs"), exist_ok=True)
    return path


def _log_tail(path: str, n: int = 1200) -> str:
    try:
        with open(path, "rb") as f:
            return f.read()[-n:].decode(errors="replace")
    except OSError:
        return ""


def _read_ready_line(proc: subprocess.Popen, tag: str, timeout: float = 60.0):
    import select
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        # select so the deadline fires even when the child prints nothing
        # (a bare readline() blocks past any timeout)
        ready, _, _ = select.select([proc.stdout], [], [], 0.25)
        if not ready:
            if proc.poll() is not None:
                raise RuntimeError(
                    f"{tag} process exited with code {proc.returncode}")
            continue
        chunk = proc.stdout.readline()
        if not chunk:
            if proc.poll() is not None:
                raise RuntimeError(
                    f"{tag} process exited with code {proc.returncode}")
            time.sleep(0.01)
            continue
        text = chunk.decode(errors="replace").strip()
        if text.startswith(tag):
            return text.split()[1:]
    proc.kill()
    raise TimeoutError(f"timed out waiting for {tag}")


class ProcessHandle:
    def __init__(self, proc: subprocess.Popen, kind: str):
        self.proc = proc
        self.kind = kind

    def alive(self) -> bool:
        return self.proc.poll() is None

    def kill(self, sig_term_first: bool = True):
        if not self.alive():
            return
        if sig_term_first:
            self.proc.terminate()
            try:
                self.proc.wait(timeout=3)
                return
            except subprocess.TimeoutExpired:
                pass
        self.proc.kill()
        self.proc.wait(timeout=5)


def start_controller(session_dir: str,
                     heartbeat_timeout_s: Optional[float] = None,
                     port: int = 0, persist: bool = True,
                     standby_of: Optional[str] = None,
                     state_dir: str = "controller_state",
                     lease_timeout_s: Optional[float] = None) -> tuple:
    """Persistence is on by default: the controller snapshots/WALs its
    metadata tables under the session dir, so a restarted controller at
    the same address resumes with actors/PGs/KV/jobs intact (reference:
    GCS restart-from-Redis, gcs_table_storage.h:357).

    ``standby_of``: boot as a HOT STANDBY of the leader at that address
    (core/ha.py) — it replicates the leader's WAL into its own
    ``state_dir`` (which must differ from the leader's) and promotes
    itself when the leader's lease lapses."""
    log_name = "controller_standby.err" if standby_of else "controller.err"
    log = open(os.path.join(session_dir, "logs", log_name), "ab")
    cmd = [sys.executable, "-m", "ray_tpu.core.controller_main",
           "--port", str(port), "--session-dir", session_dir]
    if heartbeat_timeout_s is not None:
        cmd += ["--heartbeat-timeout", str(heartbeat_timeout_s)]
    if persist:
        cmd += ["--persist-dir", os.path.join(session_dir, state_dir)]
    if standby_of:
        cmd += ["--standby-of", standby_of]
    if lease_timeout_s is not None:
        cmd += ["--lease-timeout", str(lease_timeout_s)]
    # the controller never needs a device
    proc = subprocess.Popen(
        cmd, stdout=subprocess.PIPE, stderr=log, start_new_session=True,
        env=dict(os.environ, JAX_PLATFORMS=accelerator.CPU))
    log.close()
    (addr,) = _read_ready_line(proc, "CONTROLLER_READY")
    return ProcessHandle(proc, "controller"), addr


def start_nodelet(session_dir: str, controller_addr: str,
                  resources: Optional[Dict[str, float]] = None,
                  object_store_memory: int = 0,
                  env: Optional[Dict[str, Optional[str]]] = None) -> tuple:
    import json
    log_path = os.path.join(session_dir, "logs", "nodelet.err")
    log = open(log_path, "ab")
    # JAX_PLATFORMS tells the nodelet whether it is a CPU node or one with
    # chips.  ``env`` overrides this process's environment; None removes a
    # variable (a driver pinned to the CPU starting a node that detects).
    full_env = dict(os.environ)
    for name, value in (env or {}).items():
        if value is None:
            full_env.pop(name, None)
        else:
            full_env[name] = value
    proc = subprocess.Popen(
        [sys.executable, "-m", "ray_tpu.core.nodelet_main",
         "--controller", controller_addr,
         "--session-dir", session_dir,
         "--resources", json.dumps(resources or {}),
         "--object-store-memory", str(object_store_memory)],
        stdout=subprocess.PIPE, stderr=log, start_new_session=True,
        env=full_env)
    log.close()
    try:
        addr, node_id, store_path = _read_ready_line(proc, "NODELET_READY")
    except RuntimeError as e:
        # e.g. a node that should have a chip and could not open it
        raise RuntimeError(f"{e}\n{_log_tail(log_path)}") from None
    return ProcessHandle(proc, "nodelet"), addr, node_id, store_path


def session_processes(session_dir: str) -> list:
    """Pids of every live process of a session: daemons, zygote, workers
    and agents all carry the session directory on their command line."""
    found = []
    for pid in filter(str.isdigit, os.listdir("/proc")):
        try:
            with open(f"/proc/{pid}/cmdline", "rb") as f:
                if session_dir.encode() in f.read():
                    found.append(int(pid))
        except OSError:
            pass                                  # exited meanwhile
    return found


class LocalCluster:
    """A head node: controller + one nodelet, as subprocesses."""

    def __init__(self, *, resources: Optional[Dict[str, float]] = None,
                 object_store_memory: int = 0,
                 heartbeat_timeout_s: Optional[float] = None,
                 node_env: Optional[Dict[str, Optional[str]]] = None):
        self.session_dir = new_session_dir()
        self.controller_proc, self.controller_addr = start_controller(
            self.session_dir, heartbeat_timeout_s)
        (self.nodelet_proc, self.nodelet_addr, self.node_id,
         self.store_path) = start_nodelet(
            self.session_dir, self.controller_addr, resources,
            object_store_memory, env=node_env)
        atexit.register(self.shutdown)

    def shutdown(self):
        for handle in (getattr(self, "nodelet_proc", None),
                       getattr(self, "controller_proc", None)):
            if handle is not None:
                try:
                    handle.kill()
                except Exception:
                    pass
        # A worker that sees its nodelet gone writes its span file and
        # exits: within milliseconds, but for a serve replica, whose ring
        # and whose programs' op maps take 0.5-0.7 s (PERF.md, PR 41: the
        # half second given here before cut the chip holder's files off
        # in one run of three).  Give it the grace a stopping nodelet
        # gives its workers before the sweep; the wait ends with the last
        # process.
        deadline = time.monotonic() + GlobalConfig.worker_shutdown_grace_s
        while time.monotonic() < deadline and any(
                pid != os.getpid()
                for pid in session_processes(self.session_dir)):
            time.sleep(0.01)
        # A worker orphaned while it was still starting (forked just as
        # the nodelet went down) would otherwise retry its connect for up
        # to half a minute: nothing of this session outlives shutdown.
        for pid in session_processes(self.session_dir):
            if pid != os.getpid():
                try:
                    os.kill(pid, signal.SIGKILL)
                except ProcessLookupError:
                    pass
        try:
            if os.path.exists(self.store_path):
                os.unlink(self.store_path)
        except OSError:
            pass
