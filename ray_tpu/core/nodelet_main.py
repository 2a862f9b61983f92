"""Nodelet process entrypoint (reference: src/ray/raylet/main.cc:78).

Prints ``NODELET_READY <host:port> <node_id_hex> <store_path>`` once serving.
"""

import argparse
import asyncio
import json
import sys


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--controller", required=True)
    p.add_argument("--session-dir", required=True)
    p.add_argument("--resources", default="{}",
                   help="JSON resource dict, e.g. '{\"CPU\": 8, \"TPU\": 4}'")
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=0)
    p.add_argument("--object-store-memory", type=int, default=0)
    p.add_argument("--labels", default="{}")
    args = p.parse_args()

    # `ray stack` facility: SIGUSR1 dumps every thread's Python stack to
    # stderr (per-process log file) — the reference gets this from py-spy
    # (`ray stack`, scripts.py:1712); here it's built into every runtime
    # process.
    import faulthandler
    import signal
    faulthandler.register(signal.SIGUSR1, all_threads=True)

    import os

    from . import accelerator
    from .config import GlobalConfig
    from .nodelet import Nodelet

    resources = json.loads(args.resources)
    if "CPU" not in resources:
        resources["CPU"] = float(os.cpu_count() or 1)
    # JAX_PLATFORMS as this node was started with says whether it is a
    # CPU node or one with chips; read it before the nodelet pins itself,
    # and everything that inherits its environment, to the CPU.
    reserved_platform = accelerator.reserved_platform(os.environ)
    if GlobalConfig.tpu_chips_per_host_override:
        resources.setdefault(
            "TPU", float(GlobalConfig.tpu_chips_per_host_override))
    elif GlobalConfig.tpu_autodetect:
        for k, v in accelerator.detect_tpu_resources(
                os.environ,
                timeout_s=GlobalConfig.tpu_detect_timeout_s).items():
            resources.setdefault(k, v)
    accelerator.pin_to_cpu()

    async def run():
        n = Nodelet(
            controller_addr=args.controller,
            session_dir=args.session_dir,
            resources=resources,
            host=args.host,
            port=args.port,
            object_store_memory=args.object_store_memory or None,
            labels=json.loads(args.labels),
            reserved_platform=reserved_platform,
        )
        await n.start()
        from ..util import tracing
        tracing.write_span_file_on_sigterm(args.session_dir)
        print(f"NODELET_READY {n.address} {n.node_id.hex()} {n.store_path}",
              flush=True)
        await asyncio.Event().wait()

    asyncio.run(run())


if __name__ == "__main__":
    main()
