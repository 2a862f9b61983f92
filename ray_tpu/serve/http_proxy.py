"""HTTP ingress: aiohttp proxy actor.

Capability mirror of the reference's `HTTPProxy` ASGI actors
(`serve/_private/http_proxy.py:218,312,387`, managed per node by
`http_state.py:28`): prefix-routes requests to deployments through the
in-proc Router, JSON in/out.  The server runs on a dedicated event-loop
thread inside the replica-hosting worker process; replica calls execute on
a thread pool so the accept loop never blocks on inference.
"""

from __future__ import annotations

import asyncio
import json
import threading
import time
import uuid
from concurrent.futures import ThreadPoolExecutor
from typing import Optional

from ..util import tracing


def _push_latency(deployment: str, tenant: str, ttft_s: float,
                  itl) -> None:
    """Fire-and-forget one request's TTFT/ITL sample to this node's
    nodelet (``serve_metrics`` notify, the same lane the decode engine
    uses): the nodelet folds it into the tenant-labeled
    ``ray_tpu_serve_{ttft,itl}_seconds`` histograms and runs the SLO
    evaluator.  Proxy registries are never scraped — the fold is what
    makes per-tenant latency visible cluster-wide."""
    payload = {"deployment": deployment, "tenant": tenant,
               "ttft_s": round(float(ttft_s), 6),
               "itl_s": [round(float(v), 6) for v in itl]}
    try:
        from ..core.worker_runtime import current_worker_runtime
        rt = current_worker_runtime()
        if rt is not None and rt._loop is not None:
            asyncio.run_coroutine_threadsafe(
                rt.nodelet.notify("serve_metrics", payload), rt._loop)
    except Exception:
        pass   # driver-local proxy (tests) or torn-down runtime


class HTTPProxy:
    def __init__(self, controller_handle, host: str = "127.0.0.1",
                 port: int = 8000, node_id: Optional[str] = None):
        from .router import Router
        self._router = Router(controller_handle)
        self._controller = controller_handle
        self._host = host
        self._port = port
        self._pool = ThreadPoolExecutor(max_workers=32)
        self._ready = threading.Event()
        self._startup_error: Optional[str] = None
        self._thread = threading.Thread(target=self._serve, daemon=True)
        self._thread.start()
        self._ready.wait(timeout=15.0)
        if self._startup_error:
            raise RuntimeError(self._startup_error)
        if node_id is not None:
            # PUSH the bound address to the controller (fire-and-forget):
            # the controller must never block waiting on a proxy, because
            # the proxy's own router calls back into the controller for
            # its first routing snapshot — a pull would deadlock.
            controller_handle.register_proxy.remote(node_id,
                                                    self.address())

    # -- server thread ------------------------------------------------------
    def _serve(self) -> None:
        try:
            from aiohttp import web
        except ImportError as e:  # pragma: no cover
            self._startup_error = f"aiohttp unavailable: {e}"
            self._ready.set()
            return

        from ..exceptions import ReplicaUnavailableError

        loop = asyncio.new_event_loop()
        asyncio.set_event_loop(loop)

        def unavailable(e: ReplicaUnavailableError) -> "web.Response":
            # graceful degradation: zero live replicas sheds fast as 503
            # + Retry-After, so clients/load balancers back off instead
            # of stacking doomed requests on a restarting deployment
            return web.Response(
                status=503, text=str(e),
                headers={"Retry-After":
                         str(max(1, int(round(e.retry_after_s))))})

        def prefix_of(payload):
            """Prompt tokens of a session start/resume: the router's
            prefix-affinity key (sessions sharing a system prompt land
            where that prefix's KV is hot).  Resume includes generated
            tokens — its replay prefix is what the target must hold."""
            if not isinstance(payload, dict) or \
                    payload.get("op") not in ("start", "resume"):
                return None
            p = payload.get("prompt") or []
            if p and isinstance(p[0], (list, tuple)):
                if len(p) != 1:
                    return None   # batched prompts: no single prefix
                p = p[0]
            try:
                return [int(t) for t in p] + \
                    [int(t) for t in (payload.get("generated") or ())]
            except (TypeError, ValueError):
                return None

        def route_call(name, payload, sticky=None, rid=None, t0=None):
            from ..core.config import GlobalConfig
            from ..exceptions import TaskError
            from .handle import call_with_retry
            args = (payload,) if payload is not None else ()

            def routed():
                # request arrived -> handed to the replica: route match,
                # body, the hop onto a pool thread, the router's pick
                # and the submit (again after every retry)
                tracing.record_span("proxy:route", "serve", t0,
                                    time.time(), rid=rid, deployment=name)
            try:
                return call_with_retry(
                    self._router, name, args, {},
                    timeout_s=GlobalConfig.serve_request_timeout_s,
                    sticky_replica_id=sticky,
                    prefix_tokens=(None if sticky
                                   else prefix_of(payload)),
                    request_id=rid,
                    on_assigned=routed if rid else None)
            except TaskError as e:
                # a replica-side typed shed (decode-engine admission
                # backpressure, draining engine) arrives wrapped as the
                # task error; unwrap so the 503 + Retry-After mapping —
                # and the failover client's classification — fire
                if isinstance(e.cause, ReplicaUnavailableError):
                    raise e.cause from None
                raise

        def make_call(name, payload, sticky=None, rid=None, t0=None):
            def call():
                return route_call(name, payload, sticky, rid, t0)
            return call

        async def stream_tokens(request, name, payload):
            """Server-sent-events generation (reference capability:
            Serve's StreamingResponse, serve/_private/http_util.py) —
            the PROXY drives a decode-session deployment
            (serve/decode_session.py protocol) and emits one SSE event
            per token, so clients get tokens as they decode instead of
            one request per token.

            The stream rides a :class:`FailoverSession`
            (serve/failover.py): tokens are drained via ``next_chunk``
            — ONE sid-sticky router round trip per N buffered tokens —
            and emitted one SSE event per token; the proxy journals
            every emitted token, and an owner-replica death or drain
            mid-stream is healed by a teacher-forced resume on a
            healthy replica — the client sees a stall, never an error
            and never a duplicate/missing token.  A start that replies
            ``{"error": ...}`` ends the stream with that error in band.
            A vanished CLIENT is cancelled eagerly: the loop checks the
            transport each chunk and releases the session instead of
            decoding to max_tokens into a full queue."""
            from ..core.config import GlobalConfig
            from .failover import FailoverSession
            max_new = int(payload.pop("max_new_tokens", 64))
            chunk = int(payload.pop("chunk_tokens", 0) or
                        GlobalConfig.serve_stream_chunk_tokens)
            # per-request tracing: the rid minted here rides the start
            # payload to the replica engine (underscore key = protocol
            # meta; FailoverSession replays it on resume, so a healed
            # stream keeps its id); the tenant — request field first,
            # x-tenant header second — labels the TTFT/ITL histograms,
            # cardinality-capped at the nodelet fold
            rid = uuid.uuid4().hex[:12]
            tenant = str(payload.pop("tenant", None)
                         or request.headers.get("x-tenant") or "anon")
            payload.setdefault("_rid", rid)
            t0 = time.time()
            ttft = None       # start-accepted -> first token ready
            itl = []          # gaps between consecutive SSE emissions

            def session_call(p, sticky=None):
                return route_call(name, p, sticky)

            sess = FailoverSession(session_call,
                                   {"op": "start", **payload},
                                   deployment=name)
            # the start op runs BEFORE headers go out: a failure here
            # still gets a clean HTTP 500/503 from the caller
            out = await loop.run_in_executor(self._pool, sess.start)
            sid = out.get("sid") if isinstance(out, dict) else None
            if isinstance(out, dict) and "error" not in out:
                ttft = time.time() - t0
            t_last = time.time()
            resp = web.StreamResponse(headers={
                "Content-Type": "text/event-stream",
                "Cache-Control": "no-cache"})

            async def emit(obj):
                await resp.write(
                    b"data: " + json.dumps(obj).encode() + b"\n\n")

            def client_gone():
                t = request.transport
                return t is None or t.is_closing()

            # from here the session exists and this exchange IS the
            # response: prepare() itself can raise on a dead transport,
            # so it lives INSIDE the try — every exit path must release
            # the replica's KV cache, and unrecoverable mid-stream
            # failures become in-band error events (a second Response
            # on a live stream corrupts the connection)
            try:
                await resp.prepare(request)
                await emit(out)
                emitted = len(sess.journal)  # start carried token #1
                while sess.sid is not None and emitted < max_new \
                        and not sess.done:
                    if client_gone():
                        break   # client disconnected: cancel now
                    out = await loop.run_in_executor(
                        self._pool, sess.next_tokens,
                        min(chunk, max_new - emitted))
                    for tok in out["tokens"][:max_new - emitted]:
                        await emit({"token": [tok]})
                        emitted += 1
                        now = time.time()
                        itl.append(now - t_last)
                        t_last = now
            except Exception as e:
                try:
                    await emit({"error": str(e)})
                except Exception:
                    pass    # connection already gone
            finally:
                await loop.run_in_executor(self._pool, sess.end)
            # request timeline span + one latency sample to the nodelet
            # fold — after the stream, off the token path
            try:
                tracing.record_span(
                    f"serve_request::{name}", "serve", t0, time.time(),
                    rid=rid, sid=sid, deployment=name, tenant=tenant,
                    tokens=(0 if ttft is None else 1 + len(itl)),
                    ttft_ms=(None if ttft is None
                             else round(ttft * 1e3, 3)))
            except Exception:
                pass
            if ttft is not None:
                _push_latency(name, tenant, ttft, itl)
            try:
                await resp.write(b"data: [DONE]\n\n")
                await resp.write_eof()
            except Exception:
                pass
            return resp

        async def handle(request: "web.Request") -> "web.Response":
            path = request.path
            if path == "/-/routes":
                return web.json_response(self._router.route_prefixes())
            if path == "/-/healthz":
                return web.Response(text="ok")
            t0 = time.time()
            full_path = path
            streaming = path.endswith("/stream")
            if streaming:
                path = path[:-len("/stream")]
            name = self._router.match_route(path)
            if name is None:
                # A request can beat the router's 0.25s poll TTL to a
                # just-deployed route (the table still holds the
                # boot-time snapshot); force one refresh before 404ing.
                # Costs one snapshot RPC, only on unmatched paths.
                self._router._refresh(force=True)
                name = self._router.match_route(path)
            if name is None:
                return web.Response(status=404,
                                    text=f"no deployment for {path}")
            info = self._router.route_info(name)
            ingress = info.get("ingress", False)
            if ingress and streaming:
                # the SSE decode-session lane is for token generators;
                # an ingress route ending in /stream is the
                # deployment's OWN route — re-match on the full path
                # and refresh the metadata (the re-match may land on a
                # DIFFERENT deployment than the stripped path did)
                streaming = False
                path = full_path
                name = self._router.match_route(path) or name
                info = self._router.route_info(name)
                ingress = info.get("ingress", False)
            if request.can_read_body:
                raw = await request.read()
                try:
                    payload = json.loads(raw) if raw else None
                except json.JSONDecodeError:
                    payload = raw.decode("utf-8", "replace")
            else:
                payload = None
            if ingress:
                # @serve.ingress: the deployment dispatches on the full
                # http context; body is the RAW decoded body only —
                # query params have their own field
                prefix = (info.get("route_prefix") or "/").rstrip("/")
                from .ingress import HTTP_KEY
                payload = {HTTP_KEY: {
                    "path": path[len(prefix):] or "/",
                    "method": request.method,
                    "query": dict(request.query),
                    "body": payload,
                }}
            elif payload is None and request.query:
                payload = dict(request.query)

            if streaming:
                if not isinstance(payload, dict):
                    return web.Response(
                        status=400,
                        text="/stream needs a JSON object body")
                p = payload.get("prompt")
                if isinstance(p, list) and len(p) > 1 \
                        and isinstance(p[0], list):
                    # a `grp:` group has no owner to stick to and no
                    # journal to resume from
                    return web.Response(
                        status=400, text="/stream takes one prompt")
                try:
                    return await stream_tokens(request, name, payload)
                except ReplicaUnavailableError as e:
                    return unavailable(e)
                except Exception as e:
                    return web.Response(status=500, text=str(e))

            # per-request tracing, as the SSE lane has it: the id minted
            # here rides to the replica beside the call (never in the
            # payload, which is the user's), so `proxy:request`, the
            # `proxy:route` inside it and the replica's `serve_queue::`
            # / `serve_exec::` spans join on ``rid``
            rid = uuid.uuid4().hex[:12]
            try:
                result = await loop.run_in_executor(
                    self._pool, make_call(name, payload, rid=rid, t0=t0))
            except ReplicaUnavailableError as e:
                return unavailable(e)
            except Exception as e:
                return web.Response(status=500, text=str(e))
            if isinstance(result, (bytes, bytearray)):
                resp = web.Response(body=bytes(result))
            elif isinstance(result, str):
                resp = web.Response(text=result)
            elif ingress and isinstance(result, dict) \
                    and isinstance(result.get("status"), int):
                # ingress dispatchers signal HTTP status via the
                # reserved key (404/405 must not read as 200 to load
                # balancers and monitors)
                resp = web.json_response(result, status=result["status"])
            else:
                resp = web.json_response(result)
            # write the response here rather than after returning it, so
            # the span ends when the bytes are with the socket (aiohttp
            # finds it prepared and sent, and only closes the exchange)
            try:
                await resp.prepare(request)
                await resp.write_eof()
            finally:
                tracing.record_span("proxy:request", "serve", t0,
                                    time.time(), rid=rid, deployment=name,
                                    status=resp.status)
            return resp

        app = web.Application()
        app.router.add_route("*", "/{tail:.*}", handle)
        runner = web.AppRunner(app)

        async def start():
            await runner.setup()
            site = web.TCPSite(runner, self._host, self._port)
            try:
                await site.start()
                if self._port == 0:
                    # ephemeral bind (per-node proxies on one shared
                    # host): report the real port
                    self._port = site._server.sockets[0].getsockname()[1]
            except OSError as e:
                self._startup_error = str(e)
            self._ready.set()

        async def autoscale_ticker():
            """Periodic controller nudge: the autoscale loop must tick
            through idle valleys too (scale-down to min_replicas), and
            with zero traffic nothing else polls the controller.  The
            proxy is the natural host — one exists wherever Serve
            serves HTTP, and a fire-and-forget actor call per interval
            costs nothing."""
            from ..core.config import GlobalConfig
            while True:
                iv = GlobalConfig.serve_autoscale_interval_s
                if not iv or iv <= 0:
                    await asyncio.sleep(5.0)
                    continue
                await asyncio.sleep(max(0.25, float(iv)))
                try:
                    self._controller.autoscale_tick.remote()
                except Exception:
                    pass   # controller restarting: next tick retries

        loop.run_until_complete(start())
        if not self._startup_error:
            loop.create_task(autoscale_ticker())
            loop.run_forever()

    # -- actor surface ------------------------------------------------------
    def address(self) -> str:
        return f"http://{self._host}:{self._port}"

    def node_id(self) -> Optional[str]:
        """Node actually hosting this proxy (it may not be the node of
        whoever created it — HeadOnly spawns with no affinity)."""
        try:
            from .. import api
            return api.get_runtime_context().node_id
        except Exception:
            return None

    def healthy(self) -> bool:
        return self._thread.is_alive() and not self._startup_error
