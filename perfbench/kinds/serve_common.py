"""What the two serving kinds share: the deployment (`serve.run` of one
replica that holds the chip, a `DecodeSessionCore` with the continuous-
batching engine), the HTTP load generator (one asyncio loop in the runner's
process) and the reference comparison the replica makes after the window.

A kind's own file builds the schedule (closed loop or open loop) and calls
`serve_cell`.
"""

from __future__ import annotations

import asyncio
import random
import time
from typing import Any, Callable, Dict, List

DEPLOYMENT = "bench"


# ----------------------------------------------------------- replica side

def make_deployment(serve, spec: Dict[str, Any]):
    eng = spec["traffic"]["engine"]

    @serve.deployment(name=DEPLOYMENT, max_concurrent_queries=256,
                      ray_actor_options={"num_tpus": spec["chips"]})
    class Bench:
        """Runs in the replica, the one process that holds the chip."""

        def __init__(self, spec):
            self.stamps = {"worker_ready": time.time()}
            from perfbench import chipside, manifest
            chipside.configure_jax()
            self.spec = spec
            self.c = spec["config"]
            self.family = manifest.family_of(self.c)
            self.cfg = self.family.model.model_config(
                self.c, "serve", attention_impl="auto")
            self.tracer = chipside.Tracer(spec.get("trace_dir"))
            self.calls: List[tuple] = []      # (op, start, seconds)
            self.core = None
            self._load(spec["seed"])
            self.stamps["weights"] = time.time()

        def _load(self, seed: int) -> None:
            import jax

            from perfbench import weights
            from ray_tpu.serve.config import DecodeEngineConfig
            from ray_tpu.serve.decode_session import DecodeSessionCore
            if self.core is not None:          # outputs check: next seed
                self.core.engine.shutdown()
                self.core = self.params = None
            c, model = self.c, self.family.model
            dtype = model.param_dtype(c, "serve")
            if not hasattr(self, "_make_w"):
                self._make_w = jax.jit(
                    lambda key: model.make(key, c, dtype))
            self.params = self._make_w(weights.key_of(seed))
            jax.block_until_ready(self.params)
            # the engine as a deployment gets it: default admission limits
            # (32 waiting, then 503) and default time-outs; only the slots
            # and the cache length are the cell's
            self.core = DecodeSessionCore(
                self.cfg, max_len=eng["max_len"], params=self.params,
                engine=DecodeEngineConfig(max_slots=eng["max_slots"]))

        def __call__(self, req):
            from perfbench import chipside
            op = req.get("op")
            own = getattr(self, "_op_" + str(op), None)
            if own is not None:
                return own(req)
            t0 = time.time()
            with chipside.annotate("handle:" + str(op)):
                out = self.core.handle(req)
            dt = time.time() - t0
            self.calls.append((op, t0, dt))
            if isinstance(out, dict):
                out["_srv_s"] = dt
            return out

        # -- the benchmark's own operations, none of them in the window
        def _op_warmup(self, req):
            """Every shape the window will use: one short session through
            the engine (chunk program, single-token tail, slot insert,
            decode step) and, for each prompt length of the mix, the
            slices the engine cuts a prompt into (each distinct slice of a
            device array is a small program of its own)."""
            import jax
            import jax.numpy as jnp
            chunk = self.core.engine.ecfg.prefill_chunk_tokens
            t0 = time.time()
            out = self.core.handle({"op": "start",
                                    "prompt": [1] * (chunk + 3)})
            got = len(out["token"])
            while got < 4:
                more = self.core.handle({"op": "next_chunk",
                                         "sid": out["sid"],
                                         "max_tokens": 4})
                if not more.get("tokens"):
                    raise RuntimeError(f"warm-up stream stalled: {more}")
                got += len(more["tokens"])
            self.core.handle({"op": "end", "sid": out["sid"]})
            pieces = []
            for n in sorted(set(req.get("prompt_lengths", ()))):
                # as `DecodeSessionCore.handle` and `engine.start` make
                # the device array of a prompt that arrives as a list
                p = jnp.asarray([1] * n, jnp.int32)[None]
                p = jnp.asarray(p, jnp.int32)
                off = 0
                while off < n:
                    take = chunk if n - off >= chunk else 1
                    pieces.append(p[:, off:off + take])
                    off += take
            jax.block_until_ready(pieces)
            self.stamps["warm"] = time.time()
            return {"warmup_s": time.time() - t0, "stamps": self.stamps}

        def _op_counters(self, req):
            from perfbench import chipside
            s = self.core.engine.stats()
            return {"t": time.time(), "compiles": chipside.compiles(),
                    "steps": s["steps"], "tokens": s["tokens"],
                    "prefill_chunks": s["prefill_chunks"],
                    "phase_totals": s["phase_totals"],
                    "program_shapes": s["program_shapes"],
                    "prefix": s["prefix"],
                    "starts": sum(1 for c in self.calls
                                  if c[0] == "start")}

        def _op_trace_start(self, req):
            self.tracer.start()
            return {"t": self.tracer.t_start}

        def _op_trace_stop(self, req):
            self.tracer.stop()
            return self.tracer.result()

        def _op_report(self, req):
            from perfbench import chipside
            return {"worker": chipside.report(),
                    "calls": [c for c in self.calls
                              if req["t0"] <= c[1] <= req["t1"]]}

        def _op_reseed(self, req):
            self._load(req["seed"])
            return {"seed": req["seed"]}

        def _op_verify(self, req):
            return _verify(self, req["prompts"], req["streams"],
                           bool(req.get("control")))

    return Bench.bind(spec)


def _verify(rep, prompts, streams, control: bool) -> Dict[str, float]:
    """The numbers of ``correct``, made in the replica after the window on
    a sample of the requests it served.

    The reference runs one plain full forward over prompt + stream of each
    sampled request.  The program is asked twice: its own chunked prefill
    and slot decode step are run again on the same tokens, teacher-forced,
    and their logits at every generated position compared (``logit_err``);
    and the tokens the clients received over HTTP are looked up in the
    reference's logits (``token_gap``).  With ``control`` the reference in
    fp8 stands in the program's place for both.
    """
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    from perfbench import reference
    from ray_tpu.models import (decode_step_slots, init_kv_cache,
                                init_slot_cache, prefill_chunk_jit)
    from ray_tpu.models.generate import cache_insert_slot
    c, cfg, params = rep.c, rep.cfg, rep.params
    family = rep.family
    n_new = min(len(s) for s in streams)
    streams = [list(s)[:n_new] for s in streams]
    longest = max(len(p) for p in prompts) + n_new
    width = min(family.shapes.positions(c), 32 * -(-longest // 32))
    toks = np.zeros((len(prompts), width), np.int32)
    for i, (p, s) in enumerate(zip(prompts, streams)):
        toks[i, :len(p) + n_new] = list(p) + s
    # logits[pos] predicts the token at pos + 1: the n_new emitted tokens
    # of request i are predicted at len(p) - 1 .. len(p) + n_new - 2
    first = np.asarray([len(p) - 1 for p in prompts], np.int32)
    pos = first[:, None] + np.arange(n_new)[None, :]

    def at_positions(precision):
        @jax.jit
        def f(params, toks, pos):
            lg = family.model.logits(params, toks, c, precision)
            return jnp.take_along_axis(lg, pos[:, :, None], axis=1)
        return f(params, jnp.asarray(toks), jnp.asarray(pos))

    want = at_positions("float32")                      # [n, n_new, V]
    served = jnp.asarray(streams, jnp.int32)
    if control:
        got = at_positions("fp8")
        emitted = got.argmax(-1).astype(jnp.int32)
    else:
        emitted = served
        # the program's own path, again, on the same tokens
        chunk = rep.core.engine.ecfg.prefill_chunk_tokens
        max_len = rep.core.max_len
        slots = len(prompts)
        cache = init_slot_cache(cfg, slots, max_len)
        insert = jax.jit(cache_insert_slot)
        rows = []
        for i, p in enumerate(prompts):
            pc = init_kv_cache(cfg, 1, max_len)
            arr = jnp.asarray([p], jnp.int32)
            off = 0
            while off < len(p):
                take = chunk if len(p) - off >= chunk else 1
                lg, pc = prefill_chunk_jit(params, arr[:, off:off + take],
                                           pc, cfg=cfg)
                off += take
            rows.append(lg.reshape(-1))
            cache = insert(cache, pc, jnp.int32(i))
        step = jax.jit(functools.partial(decode_step_slots, cfg=cfg))
        active = jnp.ones((slots,), bool)
        cols = [jnp.stack(rows)]
        for j in range(n_new - 1):
            lg, cache = step(params, served[:, j], cache, active)
            cols.append(lg)
        got = jnp.stack(cols, axis=1)                   # [n, n_new, V]
    v = want.shape[-1]
    out = reference.logit_numbers(got.reshape(-1, v), want.reshape(-1, v),
                                  emitted.reshape(-1))
    return {k: float(x) for k, x in out.items()}


# ------------------------------------------------------------ parent side

class Request:
    __slots__ = ("due", "prompt", "n_out", "sent", "arrivals", "tokens",
                 "calls", "done", "error", "sid")

    def __init__(self, due: float, prompt: List[int], n_out: int):
        self.due, self.prompt, self.n_out = due, prompt, n_out
        self.sent = self.done = self.error = self.sid = None
        self.arrivals: List[tuple] = []     # (epoch, tokens in the chunk)
        self.tokens: List[int] = []
        self.calls: List[tuple] = []        # (op, client s, server s)


class Client:
    """HTTP calls through the proxy on one aiohttp session."""

    def __init__(self, url: str):
        self.url = url
        self.http = None

    async def __aenter__(self):
        import aiohttp
        self.http = aiohttp.ClientSession(
            connector=aiohttp.TCPConnector(limit=0),
            timeout=aiohttp.ClientTimeout(total=900))
        return self

    async def __aexit__(self, *exc):
        await self.http.close()

    async def post(self, payload: Dict[str, Any]) -> Dict[str, Any]:
        async with self.http.post(self.url, json=payload) as resp:
            body = await resp.json(content_type=None)
            if resp.status != 200:
                raise RuntimeError(f"HTTP {resp.status}: {body}")
            return body

    async def request(self, r: Request, deadline: float) -> None:
        """One request from start to end; every arrival stamped."""
        try:
            r.sent = t0 = time.time()
            out = await self.post({"op": "start", "prompt": r.prompt})
            now = time.time()
            r.calls.append(("start", now - t0, out.get("_srv_s")))
            r.sid = out["sid"]
            r.tokens += out["token"]
            r.arrivals.append((now, len(out["token"])))
            while len(r.tokens) < r.n_out and not out.get("done") \
                    and time.time() < deadline:
                t0 = time.time()
                out = await self.post({
                    "op": "next_chunk", "sid": r.sid,
                    "max_tokens": r.n_out - len(r.tokens)})
                now = time.time()
                if "error" in out:
                    raise RuntimeError(out["error"])
                r.calls.append(("next_chunk", now - t0, out.get("_srv_s")))
                if out["tokens"]:
                    r.tokens += out["tokens"]
                    r.arrivals.append((now, len(out["tokens"])))
            r.done = time.time()
        except Exception as e:   # a failed request counts; the run goes on
            r.error = repr(e)
        finally:
            if r.sid is not None:
                try:
                    await self.post({"op": "end", "sid": r.sid})
                except Exception:
                    pass


def make_requests(traffic: Dict[str, Any], config: Dict[str, Any],
                  seed: int, dues: List[float]) -> List[Request]:
    """One request per due time.  Prompt and output lengths are fixed sets
    (quantiles of the mix's distributions); the tokens are the seed's.  The
    order the lengths are dealt in is the mix's own where it names a
    ``schedule_seed`` (every seed then times the same sequence of sizes),
    and the seed's where it does not."""
    from perfbench import manifest, stats
    order = random.Random(traffic.get("schedule_seed", seed))
    rng = random.Random(seed)
    lengths = prompt_lengths(traffic)
    n = len(dues)
    p_len = (lengths * (n // len(lengths) + 1))[:n]
    order.shuffle(p_len)
    o_len = stats.sizes(traffic["output_tokens"], n, order)
    vocab = manifest.family_of(config).shapes.vocab(config)
    return [Request(due, stats.prompt(rng, pl, vocab), ol)
            for due, pl, ol in zip(dues, p_len, o_len)]


def prompt_lengths(traffic: Dict[str, Any]) -> List[int]:
    from perfbench import stats
    return stats.quantile_set(traffic["prompt_tokens"],
                               traffic["distinct_prompt_lengths"])


def serve_cell(ctx, drive: Callable) -> Dict[str, Any]:
    """Bring the deployment up, warm it, run ``drive`` for the window,
    then collect.  ``drive(client, t_open, t_close)`` is a coroutine that
    returns the list of `Request`s it made."""
    from ray_tpu import serve
    t = ctx.traffic
    spec = {"config": ctx.config, "traffic": t, "seed": ctx.seed,
            "chips": ctx.chips, "trace_dir": ctx.trace_dir}
    serve.run(make_deployment(serve, spec))
    url = serve.api.http_address() + "/" + DEPLOYMENT
    return asyncio.run(_serve_cell(ctx, url, drive))


async def _serve_cell(ctx, url: str, drive: Callable) -> Dict[str, Any]:
    t = ctx.traffic
    async with Client(url) as client:
        warm = await client.post({"op": "warmup",
                                  "prompt_lengths": prompt_lengths(t)})
        stamps = warm["stamps"]
        if ctx.extra.get("check_seeds"):
            return await _check_many(ctx, client)
        await asyncio.sleep(t.get("settle_s", 0.0))
        before = await client.post({"op": "counters"})
        stamps["open"] = t_open = time.time()
        t_close = t_open + ctx.seconds
        tracing = None
        if ctx.trace_dir:
            tracing = asyncio.ensure_future(
                _trace_part(client, t_open, t["trace_seconds"]))
        requests = await drive(client, t_open, t_close)
        stamps["close"] = t_close
        after = await client.post({"op": "counters"})
        trace = await tracing if tracing is not None else None
        rep = await client.post({"op": "report", "t0": t_open,
                                 "t1": t_close})
        done = [r for r in requests
                if r.error is None and len(r.tokens) >= r.n_out]
        sample = done[:t["check"]["sample_requests"]]
        numbers: Dict[str, float] = {}
        if sample:
            numbers = await client.post({
                "op": "verify", "prompts": [r.prompt for r in sample],
                "streams": [r.tokens[:r.n_out] for r in sample]})
    return {"worker": rep["worker"], "stamps": stamps,
            "requests": requests, "server_calls": rep["calls"],
            "counters": {"before": before, "after": after,
                         "compiles_in_window":
                             after["compiles"] - before["compiles"]},
            "numbers": numbers, "trace": trace,
            "sanity": {"requests_completed": len(done) > 0}}


async def _trace_part(client: Client, t_open: float, seconds: float):
    """Trace ``seconds`` of the window, starting one second in."""
    await asyncio.sleep(max(0.0, t_open + 1.0 - time.time()))
    await client.post({"op": "trace_start"})
    await asyncio.sleep(seconds)
    return await client.post({"op": "trace_stop"})


async def _check_many(ctx, client: Client) -> Dict[str, Any]:
    """tools/outputs_check.py: for each seed new weights in the same
    replica, a few requests over HTTP, and the comparison; the control on
    the first few seeds."""
    t, rows = ctx.traffic, []
    n = t["check"]["sample_requests"]
    for i, seed in enumerate(ctx.extra["check_seeds"]):
        await client.post({"op": "reseed", "seed": seed})
        reqs = make_requests(t, ctx.config, seed, [0.0] * n)
        far = time.time() + 600
        await asyncio.gather(*(client.request(r, far) for r in reqs))
        bad = [r.error for r in reqs if r.error]
        if bad:
            raise RuntimeError(f"outputs check: request failed: {bad[0]}")
        body = {"op": "verify", "prompts": [r.prompt for r in reqs],
                "streams": [r.tokens[:r.n_out] for r in reqs]}
        row = {"seed": seed, "program": await client.post(body)}
        if i < ctx.extra["control_seeds"]:
            row["control"] = await client.post(dict(body, control=True))
        rows.append(row)
    rep = await client.post({"op": "report", "t0": 0, "t1": 0})
    return {"rows": rows, "worker": rep["worker"]}


# ------------------------------------------------- reductions of requests

def token_waits(requests: List[Request]) -> List[float]:
    """For every output token after a request's first, the wait since the
    previous token of that request as the client saw it; tokens that came
    in one chunk share that chunk's wait equally."""
    waits: List[float] = []
    for r in requests:
        for (t_prev, _), (t_now, k) in zip(r.arrivals, r.arrivals[1:]):
            waits += [(t_now - t_prev) / k] * k
    return waits


def first_token_waits(requests: List[Request], window_s: float
                      ) -> List[float]:
    """First token's arrival minus the time the request was DUE; a request
    that failed or got no token counts as the window's length."""
    return [(r.arrivals[0][0] - r.due) if r.arrivals and r.error is None
            else window_s for r in requests]


def tokens_in(requests: List[Request], t0: float, t1: float) -> int:
    return sum(k for r in requests for (t, k) in r.arrivals if t0 <= t <= t1)


def failed(requests: List[Request]) -> int:
    return sum(1 for r in requests
               if r.error is not None or not r.arrivals)


def loadgen_late(requests: List[Request]) -> List[float]:
    return [r.sent - r.due for r in requests if r.sent is not None]

