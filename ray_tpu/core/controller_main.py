"""Controller process entrypoint (reference: gcs_server_main.cc:40).

Prints ``CONTROLLER_READY <host:port>`` on stdout once serving, which the
launching process reads to learn the bound port.
"""

import argparse
import asyncio
import sys


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=0)
    p.add_argument("--heartbeat-timeout", type=float, default=None,
                   help="heartbeat silence before the controller acts "
                        "on a node (default: the node_death_timeout_s "
                        "config flag)")
    p.add_argument("--persist-dir", default=None,
                   help="snapshot+WAL dir for controller fault tolerance")
    p.add_argument("--standby-of", default=None,
                   help="boot as a hot standby of the leader at this "
                        "address: replicate its WAL and promote when its "
                        "lease lapses (core/ha.py)")
    p.add_argument("--session-dir", default=None,
                   help="where this controller leaves its span file "
                        "(<dir>/spans/) when it is told to stop")
    p.add_argument("--lease-timeout", type=float, default=None,
                   help="override ha_lease_timeout_s for this controller")
    args = p.parse_args()

    # `ray stack` facility: SIGUSR1 dumps every thread's Python stack to
    # stderr (per-process log file) — the reference gets this from py-spy
    # (`ray stack`, scripts.py:1712); here it's built into every runtime
    # process.
    import faulthandler
    import signal
    faulthandler.register(signal.SIGUSR1, all_threads=True)

    from .controller import Controller

    async def run():
        c = Controller(args.host, args.port, args.heartbeat_timeout,
                       persist_dir=args.persist_dir,
                       standby_of=args.standby_of,
                       lease_timeout_s=args.lease_timeout)
        await c.start()
        if args.session_dir:
            from ..util import tracing
            tracing.write_span_file_on_sigterm(args.session_dir)
        print(f"CONTROLLER_READY {c.address}", flush=True)
        await asyncio.Event().wait()

    asyncio.run(run())


if __name__ == "__main__":
    main()
