"""Worker process entrypoint (reference: python/ray/_private/workers/default_worker.py)."""

import argparse
import asyncio


def run_worker(args: dict) -> None:
    """Start the worker runtime and serve until shutdown.

    ``args`` keys: nodelet, controller, store, node_id, worker_id (hex),
    session_dir.  Shared by the exec path (`main`) and the fork-server
    path (`worker_zygote._run_child`).
    """
    import json
    import os
    os.environ["RAY_TPU_WORKER_CONTEXT"] = json.dumps({
        "controller": args["controller"], "nodelet": args["nodelet"],
        "store": args["store"], "node_id": args["node_id"],
        "session_dir": args["session_dir"]})

    # the environment is final here (a zygote-forked worker has just been
    # given its JAX_PLATFORMS), and JAX is not imported yet
    from . import accelerator
    accelerator.place_compile_cache()

    from .worker_runtime import WorkerRuntime

    async def run():
        rt = WorkerRuntime(
            nodelet_addr=args["nodelet"],
            controller_addr=args["controller"],
            store_path=args["store"],
            node_id=args["node_id"],
            worker_id=bytes.fromhex(args["worker_id"]),
            session_dir=args["session_dir"],
        )
        # SIGTERM (nodelet teardown) ships the last spans, then exits.
        # Installed BEFORE start() so a teardown racing worker spawn
        # takes the same path.
        import signal as _signal
        try:
            asyncio.get_running_loop().add_signal_handler(
                _signal.SIGTERM, rt.request_exit, 0)
        except (NotImplementedError, RuntimeError):
            pass
        await rt.start()
        await rt.run_forever()

    asyncio.run(run())


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--nodelet", required=True)
    p.add_argument("--controller", required=True)
    p.add_argument("--store", required=True)
    p.add_argument("--node-id", required=True)
    p.add_argument("--worker-id", required=True)
    p.add_argument("--session-dir", required=True)
    args = p.parse_args()

    # `ray stack` facility: SIGUSR1 dumps every thread's Python stack to
    # stderr (per-process log file) — the reference gets this from py-spy
    # (`ray stack`, scripts.py:1712); here it's built into every runtime
    # process.
    import faulthandler
    import signal
    faulthandler.register(signal.SIGUSR1, all_threads=True)

    run_worker({"nodelet": args.nodelet, "controller": args.controller,
                "store": args.store, "node_id": args.node_id,
                "worker_id": args.worker_id,
                "session_dir": args.session_dir})


if __name__ == "__main__":
    main()
