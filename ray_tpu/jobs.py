"""Job submission: run driver scripts against the cluster.

Capability mirror of the reference's job submission
(`dashboard/modules/job/job_manager.py`, `sdk.py:40,125` — submit an
entrypoint command, track status, fetch logs).  Jobs run as detached
subprocesses with stdout/stderr captured to a log file; status persists in
the controller KV so any client can query it.
"""

from __future__ import annotations

import json
import os
import subprocess
import tempfile
import time
import uuid
from typing import Any, Dict, List, Optional

from .api import _ensure_initialized

_NS = "jobs"

PENDING = "PENDING"
RUNNING = "RUNNING"
SUCCEEDED = "SUCCEEDED"
FAILED = "FAILED"


def _kv(core):
    return core.controller


def _put(core, job_id: str, info: Dict[str, Any]) -> None:
    _kv(core).call("kv_put", {"ns": _NS, "key": job_id.encode(),
                              "value": json.dumps(info).encode()})


def _get(core, job_id: str) -> Optional[Dict[str, Any]]:
    raw = _kv(core).call("kv_get", {"ns": _NS, "key": job_id.encode()})
    return json.loads(raw.decode()) if raw else None


def submit_job(entrypoint: str, *,
               runtime_env: Optional[Dict[str, Any]] = None,
               submission_id: Optional[str] = None) -> str:
    """Launch the entrypoint shell command; returns the job id."""
    core = _ensure_initialized()
    job_id = submission_id or f"job_{uuid.uuid4().hex[:10]}"
    log_dir = os.path.join(tempfile.gettempdir(), "ray_tpu_jobs")
    os.makedirs(log_dir, exist_ok=True)
    log_path = os.path.join(log_dir, f"{job_id}.log")
    env = dict(os.environ)  # a job is a driver: pinned to the CPU like us
    env["RAY_TPU_ADDRESS"] = core.controller_addr
    # init(address="auto") inside the job needs the local nodelet too
    env["RAY_TPU_NODELET"] = core.nodelet_addr
    env["RAY_TPU_SESSION_DIR"] = core.session_dir
    env["RAY_TPU_JOB_ID"] = job_id
    for k, v in (runtime_env or {}).get("env_vars", {}).items():
        env[k] = str(v)
    if "working_dir" in (runtime_env or {}):
        cwd = runtime_env["working_dir"]
    else:
        cwd = os.getcwd()
    log_f = open(log_path, "wb")
    proc = subprocess.Popen(entrypoint, shell=True, stdout=log_f,
                            stderr=subprocess.STDOUT, env=env, cwd=cwd,
                            start_new_session=True)
    _put(core, job_id, {"status": RUNNING, "pid": proc.pid,
                        "entrypoint": entrypoint, "log_path": log_path,
                        "start_time": time.time()})
    import threading

    def reap():
        code = proc.wait()
        log_f.close()
        info = _get(core, job_id) or {}
        info.update(status=SUCCEEDED if code == 0 else FAILED,
                    returncode=code, end_time=time.time())
        try:
            _put(core, job_id, info)
        except Exception:
            pass

    threading.Thread(target=reap, daemon=True).start()
    return job_id


def get_job_status(job_id: str) -> Optional[str]:
    info = _get(_ensure_initialized(), job_id)
    return info["status"] if info else None


def get_job_info(job_id: str) -> Optional[Dict[str, Any]]:
    return _get(_ensure_initialized(), job_id)


def get_job_logs(job_id: str) -> str:
    info = _get(_ensure_initialized(), job_id)
    if not info:
        raise ValueError(f"unknown job {job_id}")
    try:
        with open(info["log_path"], "r", errors="replace") as f:
            return f.read()
    except FileNotFoundError:
        return ""


def wait_job(job_id: str, timeout_s: float = 300.0) -> str:
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        st = get_job_status(job_id)
        if st in (SUCCEEDED, FAILED):
            return st
        time.sleep(0.2)
    raise TimeoutError(f"job {job_id} still {get_job_status(job_id)}")


def list_jobs() -> List[Dict[str, Any]]:
    core = _ensure_initialized()
    keys = _kv(core).call("kv_keys", {"ns": _NS, "prefix": b""})
    out = []
    for k in keys:
        info = _get(core, k.decode() if isinstance(k, bytes) else k)
        if info:
            info["job_id"] = k.decode() if isinstance(k, bytes) else k
            out.append(info)
    return out


def stop_job(job_id: str) -> bool:
    info = _get(_ensure_initialized(), job_id)
    if not info or info["status"] != RUNNING:
        return False
    import signal
    try:
        os.killpg(os.getpgid(info["pid"]), signal.SIGTERM)
        return True
    except ProcessLookupError:
        return False
