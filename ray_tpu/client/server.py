"""Client server: the head-side endpoint remote drivers attach to.

Capability mirror of the reference's Ray Client server/proxier
(/root/reference/python/ray/util/client/server/proxier.py — one endpoint
multiplexing remote clients; per-client object/actor bookkeeping).
Redesigned for the msgpack RPC stack: one `ClientServer` inside any
driver process serves every `client_*` RPC by delegating to the local
(real) CoreClient on a thread pool, holding a per-connection mirror
ObjectRef for everything the remote client can reach — dropped on the
client's release notifications or wholesale on disconnect.
"""

from __future__ import annotations

import asyncio
import concurrent.futures
import traceback
from typing import Any, Dict, Optional

from .. import exceptions
from ..core import rpc, serialization
from ..core.driver import ObjectRef
from ..core.ids import ObjectID
from ..core.task_spec import TaskSpec
from ..core.worker_runtime import _ErrorValue


class ClientServer:
    def __init__(self, host: str = "127.0.0.1", port: int = 0):
        from .. import api
        self.core = api._ensure_initialized()
        if getattr(self.core, "mode", "") == "client":
            raise RuntimeError("ClientServer needs a real driver core")
        self._pool = concurrent.futures.ThreadPoolExecutor(max_workers=16)
        self.lt = rpc.EventLoopThread("ray-tpu-client-server")
        self.server = rpc.RpcServer(host, port)
        for name in ("client_hello", "client_put", "client_get",
                     "client_wait", "client_register_function",
                     "client_submit_task", "client_create_actor",
                     "client_submit_actor_task", "client_kill_actor",
                     "client_ref_inc", "client_ref_dec", "client_timeline",
                     "client_bye", "controller_call",
                     "client_xlang_put", "client_xlang_get",
                     "client_xlang_call", "client_xlang_create_actor",
                     "client_xlang_actor_call", "client_xlang_kill_actor"):
            self.server.register(name, self._wrap(getattr(
                self, "_h_" + name[7:] if name.startswith("client_")
                else "_h_" + name)))
        self.lt.run(self.server.start())

    @property
    def address(self) -> str:
        return f"{self.server.host}:{self.server.port}"

    def _wrap(self, fn):
        async def handler(conn, data):
            loop = asyncio.get_event_loop()
            return await loop.run_in_executor(self._pool, fn, conn, data)
        return handler

    # -- per-connection mirror refs -----------------------------------------
    def _refs(self, conn) -> Dict[bytes, list]:
        table = conn.peer_info.get("client_refs")
        if table is None:
            table = conn.peer_info["client_refs"] = {}
            prev = conn.on_close

            def closed(c, prev=prev):
                if prev:
                    prev(c)
                c.peer_info.get("client_refs", {}).clear()
            conn.on_close = closed
        return table

    def _hold(self, conn, ref: ObjectRef):
        table = self._refs(conn)
        ent = table.get(ref.binary())
        if ent is None:
            table[ref.binary()] = [ref, 1]
        else:
            ent[1] += 1

    # -- handlers -------------------------------------------------------------
    def _h_hello(self, conn, data):
        return {"job_id": self.core.job_id.binary(),
                "node_id": self.core.node_id,
                "session_dir": self.core.session_dir}

    def _h_put(self, conn, data):
        value = serialization.deserialize(memoryview(data["blob"]))
        ref = self.core.put(value, xlang=data.get("xlang", False))
        self._hold(conn, ref)
        return {"object_id": ref.binary()}

    def _h_get(self, conn, data):
        refs = [ObjectRef(ObjectID(o), self.core)
                for o in data["object_ids"]]
        try:
            values = self.core.get(refs, data.get("timeout"))
        except exceptions.GetTimeoutError:
            return {"timeout": True}
        except BaseException as e:
            try:
                pickled = serialization.dumps_function(e)
            except Exception:
                pickled = None
            err = _ErrorValue(traceback.format_exc(), pickled, "client_get")
            return {"values": [serialization.serialize_to_bytes(err)]
                    * len(refs)}
        return {"values": [serialization.serialize_to_bytes(v)
                           for v in values]}

    def _h_wait(self, conn, data):
        refs = [ObjectRef(ObjectID(o), self.core)
                for o in data["object_ids"]]
        ready, not_ready = self.core.wait(refs, data["num_returns"],
                                          data.get("timeout"))
        return {"ready": [r.binary() for r in ready],
                "not_ready": [r.binary() for r in not_ready]}

    def _h_register_function(self, conn, data):
        self.core.register_function(data["fid"], data["blob"])
        return True

    def _h_submit_task(self, conn, data):
        spec = TaskSpec.from_wire(data["spec"])
        holds = [ObjectRef(ObjectID(b), self.core)
                 for b in data.get("hold_refs", [])]
        for ref in self.core.submit_task(spec, temp_refs=holds):
            self._hold(conn, ref)
        return True

    def _h_create_actor(self, conn, data):
        spec = TaskSpec.from_wire(data["spec"])
        try:
            actor_id = self.core.create_actor(
                spec, name=data.get("name"),
                detached=bool(data.get("detached")),
                get_if_exists=bool(data.get("get_if_exists")))
        except Exception as e:
            return {"error": str(e)}
        return {"actor_id": actor_id}

    def _h_submit_actor_task(self, conn, data):
        spec = TaskSpec.from_wire(data["spec"])
        self.core.attach_actor(data["actor_id"], spec.function_name)
        holds = [ObjectRef(ObjectID(b), self.core)
                 for b in data.get("hold_refs", [])]
        for ref in self.core.submit_actor_task(
                data["actor_id"], spec,
                data.get("max_task_retries", 0), temp_refs=holds):
            self._hold(conn, ref)
        return True

    def _h_kill_actor(self, conn, data):
        self.core.kill_actor(data["actor_id"],
                             data.get("no_restart", True))
        return True

    def _h_ref_inc(self, conn, data):
        for oid in data["object_ids"]:
            table = self._refs(conn)
            if oid not in table:
                # a ref the client revived from a nested value: mirror it
                table[oid] = [ObjectRef(ObjectID(oid), self.core), 1]
            else:
                table[oid][1] += 1
        return True

    def _h_ref_dec(self, conn, data):
        table = self._refs(conn)
        for oid in data["object_ids"]:
            ent = table.get(oid)
            if ent is None:
                continue
            ent[1] -= 1
            if ent[1] <= 0:
                table.pop(oid, None)  # mirror ObjectRef released by GC
        return True

    # -- cross-language (xlang) boundary ------------------------------------
    # The reference's cross-language calls (java/cpp → python) restrict the
    # data boundary to msgpack-representable values and resolve callees by
    # module path.  Same design here: these handlers let a non-Python
    # driver (ray_tpu/cpp client) put/get raw-typed values and invoke
    # Python functions/classes by "module:qualname" without speaking
    # pickle.

    @staticmethod
    def _xlang_wire(v, _depth=0):
        """Python value → msgpack-representable, or TypeError."""
        if _depth > 8:
            raise TypeError("xlang value nests too deep")
        if v is None or isinstance(v, (bool, int, float, str, bytes)):
            return v
        if isinstance(v, bytearray):
            return bytes(v)
        if isinstance(v, (list, tuple)):
            return [ClientServer._xlang_wire(x, _depth + 1) for x in v]
        if isinstance(v, dict):
            out = {}
            for k, x in v.items():
                if not isinstance(k, (str, bytes)):
                    raise TypeError(f"xlang dict key {type(k).__name__}")
                out[k] = ClientServer._xlang_wire(x, _depth + 1)
            return out
        raise TypeError(
            f"value of type {type(v).__name__} does not cross the "
            "xlang boundary (allowed: nil/bool/int/float/str/bytes/"
            "list/dict)")

    @staticmethod
    def _xlang_resolve(target: str):
        """'pkg.mod:qualname' → the named module attribute."""
        import importlib
        mod_name, _, qual = target.partition(":")
        if not mod_name or not qual:
            raise ValueError(f"xlang target must be 'module:qualname', "
                             f"got {target!r}")
        obj = importlib.import_module(mod_name)
        for part in qual.split("."):
            obj = getattr(obj, part)
        return obj

    def _h_xlang_put(self, conn, data):
        ref = self.core.put(bytes(data["blob"]))
        self._hold(conn, ref)
        return {"object_id": ref.binary()}

    def _h_xlang_get(self, conn, data):
        import time as _time
        refs = [ObjectRef(ObjectID(o), self.core)
                for o in data["object_ids"]]
        timeout = data.get("timeout")
        # per-ref gets give per-ref error granularity, but the client's
        # timeout is a TOTAL budget — track a shared deadline, not N
        # independent windows
        deadline = None if timeout is None \
            else _time.monotonic() + float(timeout)
        out = []
        for ref in refs:
            remaining = None if deadline is None \
                else max(0.0, deadline - _time.monotonic())
            try:
                value = self.core.get([ref], remaining)[0]
                out.append({"value": self._xlang_wire(value)})
            except exceptions.GetTimeoutError:
                out.append({"timeout": True})
            except Exception as e:
                out.append({"error": f"{type(e).__name__}: {e}"})
        return {"results": out}

    def _h_xlang_call(self, conn, data):
        from .. import api
        try:
            fn = self._xlang_resolve(data["function"])
            opts = {"num_returns": int(data.get("num_returns", 1))}
            if data.get("num_cpus"):
                opts["num_cpus"] = float(data["num_cpus"])
            refs = api.remote(fn).options(**opts).remote(
                *list(data.get("args", [])))
        except Exception as e:
            return {"error": f"{type(e).__name__}: {e}"}
        if not isinstance(refs, (list, tuple)):
            refs = [refs]
        for r in refs:
            self._hold(conn, r)
        return {"object_ids": [r.binary() for r in refs]}

    def _h_xlang_create_actor(self, conn, data):
        from .. import api
        try:
            cls = self._xlang_resolve(data["actor_class"])
            handle = api.remote(cls).remote(*list(data.get("args", [])))
        except Exception as e:
            return {"error": f"{type(e).__name__}: {e}"}
        actors = conn.peer_info.get("xlang_actors")
        if actors is None:
            actors = conn.peer_info["xlang_actors"] = {}
            prev = conn.on_close

            def closed(c, prev=prev):
                if prev:
                    prev(c)
                # xlang actors die with their driver connection (like the
                # reference's non-detached actors dying with the driver)
                for aid in list(c.peer_info.get("xlang_actors", {})):
                    try:
                        self.core.kill_actor(aid, True)
                    except Exception:
                        pass
                c.peer_info.get("xlang_actors", {}).clear()
            conn.on_close = closed
        actors[handle._actor_id] = handle
        return {"actor_id": handle._actor_id}

    def _h_xlang_kill_actor(self, conn, data):
        actors = conn.peer_info.get("xlang_actors", {})
        if data["actor_id"] not in actors:
            return {"error": "unknown actor (created on this connection?)"}
        try:
            self.core.kill_actor(data["actor_id"],
                                 data.get("no_restart", True))
        except Exception as e:
            # keep the handle: a failed kill must stay retryable (and the
            # close-time sweep must still cover this actor)
            return {"error": f"{type(e).__name__}: {e}"}
        actors.pop(data["actor_id"], None)
        return {"ok": True}

    def _h_xlang_actor_call(self, conn, data):
        handle = conn.peer_info.get("xlang_actors", {}).get(
            data["actor_id"])
        if handle is None:
            return {"error": "unknown actor (created on this connection?)"}
        try:
            ref = getattr(handle, data["method"]).remote(
                *list(data.get("args", [])))
        except Exception as e:
            return {"error": f"{type(e).__name__}: {e}"}
        self._hold(conn, ref)
        return {"object_ids": [ref.binary()]}

    def _h_timeline(self, conn, data):
        from ..util import tracing
        return tracing.span_events()

    def _h_bye(self, conn, data):
        self._refs(conn).clear()
        return True

    def _h_controller_call(self, conn, data):
        return self.core.controller.call(data["method"], data.get("data"),
                                         timeout=60)

    def stop(self):
        try:
            self.lt.run(self.server.stop())
        except Exception:
            pass
        self.lt.stop()


def serve(port: int = 0, host: str = "127.0.0.1") -> ClientServer:
    """Start a client endpoint inside the current driver (the head)."""
    return ClientServer(host, port)


def main():
    import argparse
    import signal

    from .. import api

    p = argparse.ArgumentParser()
    p.add_argument("--address", required=True,
                   help="controller address host:port")
    p.add_argument("--nodelet", required=True,
                   help="a nodelet address host:port for this host")
    p.add_argument("--port", type=int, default=10001)
    args = p.parse_args()
    api.init(address=args.address, nodelet_addr=args.nodelet)
    s = ClientServer("0.0.0.0", args.port)
    print(f"CLIENT_SERVER_READY {s.address}", flush=True)
    signal.pause()


if __name__ == "__main__":
    main()
