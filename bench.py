"""Measurement scripts that predate the benchmark.

Default mode: gpt2-medium training throughput on ONE TPU chip
(microbatch 4 x accum 16, bf16 compute, Pallas flash attention, AdamW),
reported as model-FLOP utilization against the chip's published peak
(`ray_tpu.util.device_profile.PEAK_TFLOPS`).  It needs a chip: the parent
stays off JAX, starts one child pinned to ``JAX_PLATFORMS=tpu``, and exits
non-zero when that child cannot get a TPU.  Nothing is replayed, salvaged
or run on the CPU in its place.

The other modes (``--serve``, ``--serve-breakdown``, ``--spec-bench``,
``--autoscale-bench``, ``--rl``, ``--attr``) measure host-side paths on the
CPU backend and say so in their output.

`chip_smoke.py` is the proof that the system runs on the chip; a benchmark
with cells and a ledger replaces this file.
"""

import json
import os
import subprocess
import sys
import time
import traceback

_CHILD_FLAG = "_BENCH_CHILD"   # value: the mode the child runs
_TPU_ATTEMPT_TIMEOUT = 1500


def timed_steps(step, params, opt_state, data, steps):
    """Host seconds for ``steps`` train steps, ended by
    ``block_until_ready`` on the last step's outputs (each step consumes
    the one before, so the last one finishing means all have).  Returns
    ``(seconds, params, opt_state)``: the step donates its inputs."""
    import jax
    t0 = time.perf_counter()
    for _ in range(steps):
        params, opt_state, m = step(params, opt_state, data)
    jax.block_until_ready((params, opt_state, m))
    return time.perf_counter() - t0, params, opt_state


def _run_measurement() -> dict:
    """The default mode's body; runs in the child that holds the chip."""
    t_start = time.perf_counter()

    def log(msg: str) -> None:
        print(f"[bench {time.perf_counter() - t_start:7.1f}s] {msg}",
              file=sys.stderr, flush=True)

    import jax
    import jax.numpy as jnp
    import optax

    from ray_tpu.models import (TransformerConfig, flops_per_token,
                                init_params, make_train_step)
    from ray_tpu.util.device_profile import DispatchProfiler, peak_flops_of

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"bench.py measures a TPU; JAX gave {dev.platform}")
    peak = peak_flops_of(dev.device_kind)
    log(f"devices={jax.devices()}")
    # flash blocks 1024x1024, norm_remat, loss_chunk, bf16 Adam-mu and
    # in-step accumulation: the recipe of the 2026-08 capture
    # (BENCH_TPU_CAPTURE.json), kept so the next number is comparable
    os.environ.setdefault("RAY_TPU_FLASH_BLOCK_Q", "1024")
    os.environ.setdefault("RAY_TPU_FLASH_BLOCK_K", "1024")
    cfg = TransformerConfig.gpt2("medium", remat=False,
                                 loss_chunk=128, norm_remat=True)
    batch, seq, steps, accum = 64, 1024, 6, 16

    params, _ = init_params(jax.random.PRNGKey(0), cfg)
    opt = optax.adamw(3e-4, weight_decay=0.1, mu_dtype=jnp.bfloat16)
    opt_state = opt.init(params)
    # the profiler shim only syncs on first-seen shapes (sample_every is
    # effectively off), so it adds the compile ledger and no stall
    prof = DispatchProfiler(sample_every=10 ** 9)
    step = prof.wrap("train_step",
                     jax.jit(make_train_step(cfg, opt, accum_steps=accum),
                             donate_argnums=(0, 1)))
    data = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (batch, seq),
                                         0, cfg.vocab_size)}
    log("warmup: compiling + 2 steps...")
    _, params, opt_state = timed_steps(step, params, opt_state, data, 2)
    log(f"warmup done; measuring {steps} steps")
    dt, params, opt_state = timed_steps(step, params, opt_state, data, steps)

    tokens_per_step = batch * seq
    flops_tok = flops_per_token(cfg, seq)
    mfu = steps * tokens_per_step / dt * flops_tok / peak
    prof.set_flops_per_token("train_step", flops_tok)
    prof.note_tokens("train_step", (2 + steps) * tokens_per_step)
    del params, opt_state, data, step
    return {
        "metric": "gpt2_train_mfu",
        "value": round(mfu, 4),
        "unit": "fraction_of_peak",
        "vs_baseline": round(mfu / 0.40, 4),
        "detail": {
            "model": "gpt2-medium(355M) m4_a16",
            "device": {"platform": dev.platform, "kind": dev.device_kind,
                       "count": len(jax.devices())},
            "tokens_per_s": round(steps * tokens_per_step / dt, 1),
            "step_ms": round(1000 * dt / steps, 2),
            "batch": batch, "accum": accum,
            "train_profile": prof.snapshot(peak),
            "scaling": _scaling_rows_on_chip(log, peak)},
    }


def _scaling_rows_on_chip(log, peak: float) -> dict:
    """The same recipe at gpt2-small (long batch, long context) and
    llama-1b, for continuity with the 2026-08 capture's rows."""
    import jax
    import jax.numpy as jnp
    import optax

    from ray_tpu.models import (TransformerConfig, flops_per_token,
                                init_params, make_train_step)
    rows = {}
    for name, preset, batch, seq, accum in (
            ("small_m16_a8_s1024", "small", 128, 1024, 8),
            ("small_b4_s4096", "small", 4, 4096, 1),
            ("llama1b_bf16p_b4_dots", "llama1b", 4, 1024, 1)):
        log(f"scaling: {name} compiling...")
        if preset == "llama1b":
            # 1.5B params: bf16 params + dots remat (fp32 + Adam does not
            # fit 16 GB)
            cfg = TransformerConfig.llama(
                "1b", max_seq_len=1024, remat="dots", norm_remat=True,
                loss_chunk=128, param_dtype=jnp.bfloat16)
        else:
            cfg = TransformerConfig.gpt2(preset, remat=False,
                                         loss_chunk=128, norm_remat=True,
                                         max_seq_len=max(1024, seq))
        params, _ = init_params(jax.random.PRNGKey(0), cfg)
        opt = optax.adamw(3e-4, weight_decay=0.1, mu_dtype=jnp.bfloat16)
        opt_state = opt.init(params)
        step = jax.jit(make_train_step(cfg, opt, accum_steps=accum),
                       donate_argnums=(0, 1))
        data = {"tokens": jax.random.randint(jax.random.PRNGKey(1),
                                             (batch, seq), 0,
                                             cfg.vocab_size)}
        _, params, opt_state = timed_steps(step, params, opt_state, data, 2)
        steps = 12
        dt, params, opt_state = timed_steps(step, params, opt_state, data,
                                            steps)
        mfu = steps * batch * seq / dt * flops_per_token(cfg, seq) / peak
        rows[name] = {"mfu": round(mfu, 4),
                      "step_ms": round(1000 * dt / steps, 1),
                      "batch": batch, "accum": accum,
                      "tok_s": round(steps * batch * seq / dt)}
        log(f"scaling: {name} mfu={rows[name]['mfu']}")
        del params, opt_state, step, data
    return rows


def _run_serve_measurement() -> dict:
    """Serve north star #5: generation TTFT + decode throughput through
    the FULL serving path — HTTP proxy → router → replica holding a KV
    cache (reference: /root/reference/doc/source/serve/performance.md:19
    documents its stack's serving latencies the same way).

    Runs at a tiny model on the CPU backend: what it measures is the
    serving path's own overhead, not a device time.
    """
    import ray_tpu
    from ray_tpu import serve

    ray_tpu.init(num_cpus=4)
    serve.start()

    @serve.deployment(max_concurrent_queries=8)
    class Generator:
        def __init__(self):
            import jax.numpy as jnp

            from ray_tpu.models import TransformerConfig
            from ray_tpu.serve.decode_session import DecodeSessionCore
            self.core = DecodeSessionCore(
                TransformerConfig.tiny(max_seq_len=256,
                                       dtype=jnp.float32), max_len=256)

        def __call__(self, req):
            return self.core.handle(req)

    import requests
    serve.run(Generator.bind(), name="generate")
    addr = serve.api.http_address()
    prompt_len, decode_steps = 64, 16
    # keep-alive session: a real streaming client holds its connection,
    # so per-request TCP setup must not inflate the measured path
    http = requests.Session()

    def session(i: int):
        """→ (ttft_s, [per-token decode seconds])  — distinct prompts
        per session so no cache anywhere can fake the numbers."""
        prompt = [(7 * i + j) % 250 for j in range(prompt_len)]
        t0 = time.perf_counter()
        r = http.post(f"{addr}/generate",
                      json={"op": "start", "prompt": prompt},
                      timeout=180)
        ttft = time.perf_counter() - t0
        r.raise_for_status()
        sid = r.json()["sid"]
        per_tok = []
        for _ in range(decode_steps):
            t0 = time.perf_counter()
            http.post(f"{addr}/generate",
                      json={"op": "next", "sid": sid},
                      timeout=60).raise_for_status()
            per_tok.append(time.perf_counter() - t0)
        # release the KV cache (sessions are real replica memory)
        http.post(f"{addr}/generate", json={"op": "end", "sid": sid},
                  timeout=60)
        return ttft, per_tok

    session(0)                       # warmup: compiles prefill + decode
    ttfts, decodes = [], []
    for i in range(1, 21):
        ttft, per_tok = session(i)
        ttfts.append(ttft)
        decodes.extend(per_tok)
    import numpy as np
    p50 = float(np.percentile(ttfts, 50)) * 1e3
    p90 = float(np.percentile(ttfts, 90)) * 1e3
    dec_p50 = float(np.percentile(decodes, 50)) * 1e3
    streaming = _measure_concurrent_streaming(http, addr, prompt_len)
    serve.shutdown()
    ray_tpu.shutdown()
    return {
        "metric": "serve_gen_ttft_ms_p50", "value": round(p50, 2),
        "unit": "ms",
        # the serving path itself is the measured quantity; 100 ms is
        # the reference's own interactive-serving yardstick
        # (performance.md: "latencies ... under 100ms" for its proxy)
        "vs_baseline": round(100.0 / max(p50, 1e-6), 4),
        "detail": {"p90_ttft_ms": round(p90, 2),
                   "decode_ms_per_tok_p50": round(dec_p50, 2),
                   "decode_tok_s": round(1000.0 / max(dec_p50, 1e-6), 1),
                   "sessions": 20, "prompt_len": prompt_len,
                   "path": "http_proxy->router->replica",
                   "model": "transformer-tiny(cpu harness)",
                   "streaming": streaming,
                   "note": ("host path overhead on the CPU backend, not "
                            "a device time; 'streaming' is the "
                            "continuous-batching SSE lane (chunked "
                            "next_chunk drains) at 1/4/8 concurrent "
                            "sessions")},
    }


def _measure_concurrent_streaming(http, addr: str,
                                  prompt_len: int) -> dict:
    """Continuous-batching serve benchmark: N concurrent SSE streams
    through `/generate/stream` (replica decode engine + chunked
    `next_chunk` drains + sid-sticky routing).  Reports per-N
    ``agg_tok_s`` (total tokens / wall) and ``stream_ms_per_tok_p50``
    (per-session wall per token) — the serve-side counterpart of the
    raw `llama1b_b8_scan` batched-decode headline."""
    import threading

    import numpy as np
    import requests
    max_new = 32

    def stream_one(i: int, out: dict) -> None:
        prompt = [(11 * i + j) % 250 for j in range(prompt_len)]
        tokens = 0
        t0 = time.perf_counter()
        with requests.post(f"{addr}/generate/stream",
                           json={"prompt": prompt,
                                 "max_new_tokens": max_new},
                           stream=True, timeout=300) as r:
            r.raise_for_status()
            for line in r.iter_lines():
                if line.startswith(b"data: ") and b'"token"' in line:
                    tokens += 1
        out[i] = (time.perf_counter() - t0, tokens)

    stream_one(0, {})                # warmup: engine slot-step compile
    result = {}
    for n in (1, 4, 8):
        out: dict = {}
        threads = [threading.Thread(target=stream_one, args=(i, out))
                   for i in range(n)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        total = sum(tok for _, tok in out.values())
        per_tok = [dur / max(tok, 1) for dur, tok in out.values()]
        result[f"s{n}"] = {
            "agg_tok_s": round(total / max(wall, 1e-9), 1),
            "stream_ms_per_tok_p50":
                round(float(np.percentile(per_tok, 50)) * 1e3, 2),
            "sessions": n, "tokens": total,
        }
    return result


def _run_spec_bench() -> dict:
    """`--spec-bench`: the PR-6 model-side serve optimisations, at the
    ENGINE level (DecodeSessionCore.handle, no cluster/HTTP) so the
    numbers isolate the data plane the optimisations live in.

    * ``spec_ab``: ms/tok for N concurrent streams with speculative
      decoding on vs off, asserting byte-identical output.  The draft
      is the target's FIRST LAYER and the target's second-layer output
      projections are zeroed — an exact distillation pair (the only way
      untrained weights admit a cheap high-acceptance draft; a random
      independent draft measures ~1% acceptance and a weight-shared
      draft pays full-size proposal compute).  Every measured FLOP is
      really executed: the target runs both layers, the draft one.  On
      chip the draft is a real small model, e.g. gpt2s for llama-1b.
      The win is 2 dispatches per 1..k accepted tokens vs 1 per token,
      plus the k-wide verify forward batching what k single steps
      would compute.
    * ``ttft_under_load``: a long-prompt session joins a saturated
      8-session batch; reports the joiner's TTFT and the worst stall it
      inflicts on incumbent streams, vs their steady chunk cadence —
      chunked admission bounds that stall at ~one chunk program.
    """
    import dataclasses
    import threading

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import TransformerConfig, init_params
    from ray_tpu.serve.config import DecodeEngineConfig
    from ray_tpu.serve.decode_session import DecodeSessionCore

    cfg = TransformerConfig.tiny(max_seq_len=256, n_layers=4,
                                 attention_impl="reference",
                                 dtype=jnp.float32)
    params, _ = init_params(jax.random.PRNGKey(5), cfg)
    # distillation pair at a realistic 4:1 compute ratio: zero layers
    # 2-4's output projections (the layers still RUN — their residual
    # contribution is exactly 0), so the 1-layer draft slice computes
    # the same function at a quarter of the FLOPs and acceptance sits
    # near 1.0
    layers = dict(params["layers"])
    for key in ("wo", "w_out"):
        layers[key] = layers[key].at[1:].set(0.0)
    params = {**params, "layers": layers}
    draft_cfg = dataclasses.replace(cfg, n_layers=1)
    draft_params = {**params, "layers": jax.tree_util.tree_map(
        lambda x: x[:1], layers)}
    # prompt = exactly one [1, 32] chunk block and a long decode tail
    # (sessions run to cache cap): the A/B isolates the decode path —
    # admission cost is identical on both sides and measured separately
    # by ttft_under_load.  Token queues are deeper than the stream so
    # the engine never pauses and the timed window is pure engine
    # throughput (client drains happen after, untimed, for the parity
    # assertion — concurrent polling only adds equal GIL noise to both
    # sides).
    max_len, nsess = 224, 4
    prompts = [[(11 * i + j) % 250 for j in range(32)]
               for i in range(nsess)]

    def run_core(core):
        r = core.handle({"op": "start", "prompt": list(range(32))})
        while True:                   # warmup: compiles every program
            o = core.handle({"op": "next_chunk", "sid": r["sid"],
                             "max_tokens": 8, "timeout_s": 10.0})
            if o["tokens"] or o.get("done"):
                break
        core.handle({"op": "end", "sid": r["sid"]})
        time.sleep(0.2)
        rs = [core.handle({"op": "start", "prompt": p})
              for p in prompts]
        st0 = core.handle({"op": "stats"})["engine"]
        t0 = time.perf_counter()
        while core.handle({"op": "stats"})["engine"]["occupied_slots"]:
            time.sleep(0.005)
        wall = time.perf_counter() - t0
        st1 = core.handle({"op": "stats"})["engine"]
        outs = []
        for r in rs:
            toks = list(r["token"])
            while True:
                o = core.handle({"op": "next_chunk", "sid": r["sid"],
                                 "max_tokens": 256})
                toks += o["tokens"]
                if o["done"]:
                    break
            core.handle({"op": "end", "sid": r["sid"]})
            outs.append(toks)
        toks_decoded = max(1, st1["tokens"] - st0["tokens"])
        return wall / toks_decoded * 1e3, outs, st1

    k = 12
    core_off = DecodeSessionCore(
        cfg, max_len=max_len, seed=5, params=params,
        engine=DecodeEngineConfig(max_slots=nsess,
                                  token_queue_depth=256))
    core_on = DecodeSessionCore(
        cfg, max_len=max_len, seed=5, params=params,
        engine=DecodeEngineConfig(max_slots=nsess,
                                  token_queue_depth=256,
                                  spec_draft=(draft_cfg, draft_params),
                                  spec_k=k))
    # best-of-3 interleaved rounds: a fresh process carries
    # allocator/XLA warm-up noise and CPU scheduling jitter moves
    # single rounds by ±40%; the per-core minimum is stable
    ms_off, outs_off, _ = run_core(core_off)
    ms_on, outs_on, st = run_core(core_on)
    for _ in range(2):
        ms_off = min(ms_off, run_core(core_off)[0])
        ms_on = min(ms_on, run_core(core_on)[0])
    assert outs_on == outs_off, \
        "speculative decode changed the token stream"
    core_off.engine.shutdown()
    core_on.engine.shutdown()
    spec_ab = {
        "sessions": nsess,
        "tokens_per_stream": len(outs_on[0]), "spec_k": k,
        "spec_off_ms_per_tok": round(ms_off, 3),
        "spec_on_ms_per_tok": round(ms_on, 3),
        "speedup": round(ms_off / max(ms_on, 1e-9), 2),
        "ratio_on_over_off": round(ms_on / max(ms_off, 1e-9), 3),
        "ms_per_tok_is": "aggregate engine decode wall per token, "
                         "4 concurrent slots",
        "acceptance": st["spec"]["acceptance"],
        "draft": "exact-distillation pair: draft = target's first "
                 "layer, target's 2nd-layer output projections zeroed "
                 "(untrained harness weights admit no other cheap "
                 "high-acceptance draft; on chip: gpt2s drafts for "
                 "llama-1b)",
        "output_identical": True,
    }

    # ---- TTFT under load: join a saturated batch with a long prompt.
    # Incumbents get a deep cache (long runway) and the poller lanes
    # record chunk-arrival timestamps continuously, so the joiner's
    # admission lands mid-stream and its inflicted stall is readable
    # from the incumbents' inter-chunk gaps.
    chunk_tokens = 32
    incumbents, joiner_prompt_len = 8, 128
    cfg2 = TransformerConfig.tiny(max_seq_len=2048,
                                  attention_impl="reference",
                                  dtype=jnp.float32)
    core = DecodeSessionCore(
        cfg2, max_len=2048, seed=5,
        engine=DecodeEngineConfig(max_slots=incumbents + 1,
                                  prefill_chunk_tokens=chunk_tokens))
    # warm every program shape the measurement touches ([1,32] blocks +
    # [1,1] tail + the decode step) so the joiner's TTFT is admission,
    # not compilation
    w = core.handle({"op": "start",
                     "prompt": [(3 + j) % 250 for j in range(80)]})
    while True:
        o = core.handle({"op": "next_chunk", "sid": w["sid"],
                         "max_tokens": 8})
        if o["tokens"] or o.get("done"):
            break
    core.handle({"op": "end", "sid": w["sid"]})

    stop = threading.Event()
    arrivals = [[] for _ in range(incumbents)]   # chunk arrival stamps

    def incumbent(i):
        r = core.handle({"op": "start",
                         "prompt": [(7 * i + j) % 250
                                    for j in range(40)]})
        while not stop.is_set():
            o = core.handle({"op": "next_chunk", "sid": r["sid"],
                             "max_tokens": 4, "timeout_s": 5.0})
            if o.get("done") or "error" in o:
                break
            if o["tokens"]:
                arrivals[i].append(time.perf_counter())
        core.handle({"op": "end", "sid": r["sid"]})

    threads = [threading.Thread(target=incumbent, args=(i,))
               for i in range(incumbents)]
    for t in threads:
        t.start()
    deadline = time.monotonic() + 60
    while time.monotonic() < deadline and \
            any(len(lane) < 30 for lane in arrivals):
        time.sleep(0.05)              # all lanes streaming steadily
    t_join = time.perf_counter()
    r = core.handle({"op": "start",
                     "prompt": [(13 + j) % 250
                                for j in range(joiner_prompt_len)]})
    ttft_ms = (time.perf_counter() - t_join) * 1e3
    time.sleep(0.5)
    stop.set()
    core.handle({"op": "end", "sid": r["sid"]})
    for t in threads:
        t.join(timeout=30)
    core.engine.shutdown()

    pre_gaps, join_gaps = [], []
    join_end = t_join + ttft_ms / 1e3
    for lane in arrivals:
        for t0, t1 in zip(lane, lane[1:]):
            if t1 < t_join:
                pre_gaps.append(t1 - t0)
            elif t1 <= join_end + 0.25:
                join_gaps.append(t1 - t0)
    steady_ms = float(np.percentile(pre_gaps, 50)) * 1e3 \
        if pre_gaps else 0.0
    worst_ms = float(np.max(join_gaps)) * 1e3 if join_gaps else 0.0
    stall_ms = max(0.0, worst_ms - steady_ms)
    ttft_load = {
        "incumbents": incumbents,
        "joiner_prompt_len": joiner_prompt_len,
        "prefill_chunk_tokens": chunk_tokens,
        "joiner_ttft_ms": round(ttft_ms, 2),
        "incumbent_chunk_interval_ms_p50": round(steady_ms, 2),
        "incumbent_worst_gap_during_join_ms": round(worst_ms, 2),
        "joiner_inflicted_stall_ms": round(stall_ms, 2),
        "stall_lt_chunk_interval": bool(stall_ms < max(steady_ms, 1e-9)),
    }
    return {"spec_ab": spec_ab, "ttft_under_load": ttft_load}


def _spec_bench_main() -> None:
    """`python bench.py --spec-bench`: run the PR-6 measurements and
    merge them into SERVE_BENCH.json's detail (the headline serve
    record stays the full-path `--serve` run)."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    try:
        result = _run_spec_bench()
    except Exception:
        result = {"error": traceback.format_exc(limit=3)}
    print(json.dumps(result))
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "SERVE_BENCH.json")
    try:
        with open(path) as f:
            ledger = json.load(f)
    except Exception:
        ledger = {"metric": "serve_gen_ttft_ms_p50", "detail": {}}
    ledger.setdefault("detail", {}).update(result)
    try:
        with open(path, "w") as f:
            json.dump(ledger, f)
    except OSError:
        pass


def _run_autoscale_bench() -> dict:
    """`--autoscale-bench`: bursty multi-tenant chat through the FULL
    path (HTTP SSE -> proxy -> prefix-affinity router -> autoscaled
    engine replicas).  Sessions share a long system prompt and join/
    leave in phases; the ledger records the replica-count-vs-load
    timeline (the autoscaler tracking the burst and draining back
    down), prefix-hit vs cold TTFT on a warm replica, and that every
    stream completed with zero user-visible errors — scale-downs drain
    via live-session migration, never drop."""
    import threading

    import requests

    import ray_tpu
    from ray_tpu import serve, state
    from ray_tpu.serve.config import AutoscalingConfig

    ray_tpu.init(num_cpus=8)
    serve.start()

    @serve.deployment(
        max_concurrent_queries=32,
        autoscaling_config=AutoscalingConfig(
            min_replicas=1, max_replicas=3,
            occupancy_high=0.7, occupancy_low=0.25,
            target_occupancy=0.6, trend_window_s=4.0,
            upscale_delay_s=0.0, downscale_delay_s=2.0))
    class Chat:
        def __init__(self):
            import jax.numpy as jnp

            from ray_tpu.models import TransformerConfig
            from ray_tpu.serve.config import DecodeEngineConfig
            from ray_tpu.serve.decode_session import DecodeSessionCore
            self.core = DecodeSessionCore(
                TransformerConfig.tiny(max_seq_len=512,
                                       attention_impl="reference",
                                       dtype=jnp.float32),
                max_len=512,
                engine=DecodeEngineConfig(
                    max_slots=2, token_queue_depth=4, max_waiting=32,
                    admission_timeout_s=180.0))

        def engine_stats(self):
            return self.core.handle({"op": "stats"})

        def __call__(self, req):
            return self.core.handle(req)

    serve.run(Chat.bind(), name="chat")
    addr = serve.api.http_address()
    system = [(13 * j) % 250 for j in range(320)]   # shared sys prompt

    live = {"n": 0}
    live_lock = threading.Lock()
    timeline = []
    stop_sampler = threading.Event()

    def sampler():
        while not stop_sampler.is_set():
            try:
                reps = serve.list_deployments()["chat"]["num_replicas"]
            except Exception:
                reps = -1
            with live_lock:
                n = live["n"]
            timeline.append({"t": round(time.perf_counter() - t_base, 2),
                             "replicas": reps, "live_sessions": n})
            stop_sampler.wait(0.5)

    errors = []

    def stream(i, tokens=120, pace=0.04, suffix=None):
        """One paced SSE chat turn; returns (ttft_s, tokens_seen)."""
        prompt = system + (suffix or [251, (i * 3) % 250, i % 250])
        with live_lock:
            live["n"] += 1
        try:
            t0 = time.perf_counter()
            ttft = None
            seen = 0
            with requests.post(
                    f"{addr}/chat/stream",
                    json={"prompt": prompt, "max_new_tokens": tokens},
                    stream=True, timeout=600) as r:
                if r.status_code != 200:
                    errors.append(f"s{i}: HTTP {r.status_code}")
                    return None, 0
                for line in r.iter_lines():
                    if not line.startswith(b"data: "):
                        continue
                    body = line[len(b"data: "):]
                    if body == b"[DONE]":
                        break
                    ev = json.loads(body)
                    if "error" in ev:
                        errors.append(f"s{i}: {ev['error']}")
                        break
                    if "token" in ev:
                        if ttft is None:
                            ttft = time.perf_counter() - t0
                        seen += 1
                        time.sleep(pace)   # paced client: session lives
            if seen < tokens:
                errors.append(f"s{i}: {seen}/{tokens} tokens")
            return ttft, seen
        finally:
            with live_lock:
                live["n"] -= 1

    t_base = time.perf_counter()
    sam = threading.Thread(target=sampler, daemon=True)
    sam.start()
    # phase 1 — single tenant (warms compiles; fleet stays at min)
    stream(0, tokens=30, pace=0.0)
    # phase 2 — burst: 8 tenants sharing the system prompt join inside
    # 2s; slots saturate, waiting depth climbs, the fleet must grow
    threads = []
    burst_ttfts = []

    def one(i):
        ttft, _ = stream(i, tokens=120, pace=0.04)
        if ttft is not None:
            burst_ttfts.append(ttft)
    for i in range(1, 9):
        th = threading.Thread(target=one, args=(i,))
        th.start()
        threads.append(th)
        time.sleep(0.25)
    for th in threads:
        th.join(timeout=600)
    peak = max((p["replicas"] for p in timeline), default=1)
    # phase 3 — idle: the fleet must drain back to min via the
    # retirement path (ticks come from the proxy's autoscale nudge)
    deadline = time.perf_counter() + 60
    final = peak
    while time.perf_counter() < deadline:
        try:
            final = serve.list_deployments()["chat"]["num_replicas"]
        except Exception:
            pass
        if final == 1:
            break
        time.sleep(0.5)
    # phase 4 — prefix-hit vs cold TTFT on the now-stable warm fleet
    # (measuring mid-retirement would fold scale-down sheds into the
    # numbers): seed one donor session with the system prompt, then
    # A-B streams whose only difference is whether their 320-token
    # prefix is resident in a slot
    cold_ttfts, hit_ttfts = [], []
    _sse_ttft(requests, addr, system + [250], 4)     # donor seed
    # hits first, back to back: each admission gathers the 320-token
    # prefix from its predecessor's slot (slots are LIFO-reused, so
    # interleaving colds here would evict the donor between hits)
    for i in range(3):
        th, _ = _sse_ttft(requests, addr, system + [252, i], 8)
        if th is not None:
            hit_ttfts.append(th)
    for i in range(3):
        # cold: a prompt sharing NOTHING with any resident prefix
        cold_prompt = [(97 * (i + 1) + j) % 250 for j in range(320)]
        tc, _ = _sse_ttft(requests, addr, cold_prompt + [i], 8)
        if tc is not None:
            cold_ttfts.append(tc)
    # prefix-cache hit accounting straight from the engines
    hits = reused = 0
    try:
        # engine stats are per replica and the handle load-balances:
        # sample several times and keep the busiest replica's counts
        # (a conservative floor on fleet-wide hits)
        h = serve.get_handle("chat")
        for _ in range(8):
            st = h.engine_stats.remote().result(timeout_s=30.0)
            eng = (st or {}).get("engine") or {}
            pfx = eng.get("prefix") or {}
            if pfx.get("applied_hits", 0) >= hits:
                hits = pfx.get("applied_hits", 0)
                reused = pfx.get("tokens_reused", 0)
    except Exception:
        pass
    stop_sampler.set()
    sam.join(timeout=5)
    # per-deployment occupancy series through the satellite API (the
    # same series the autoscale loop trended)
    series_pts = 0
    try:
        hist = state.metrics_history(
            name="ray_tpu_serve_engine_occupied_slots",
            deployment="chat", kind="gauges")
        series_pts = sum(len(v) for v in hist.get("series", {}).values())
    except Exception:
        pass
    serve.shutdown()
    ray_tpu.shutdown()
    import numpy as np
    med = (lambda xs: round(float(np.median(xs)) * 1e3, 1)
           if xs else None)
    return {
        "peak_replicas": peak, "final_replicas": final,
        "burst_sessions": 8, "errors": errors[:10],
        "zero_user_visible_errors": not errors,
        "burst_ttft_ms_p50": med(burst_ttfts),
        "cold_ttft_ms_p50": med(cold_ttfts),
        "prefix_hit_ttft_ms_p50": med(hit_ttfts),
        "prefix_applied_hits": hits,
        "prefix_tokens_reused": reused,
        "occupancy_series_points": series_pts,
        "timeline": timeline,
    }


def _sse_ttft(requests, addr, prompt, tokens):
    """TTFT of one unpaced SSE stream (helper for the cold/hit A-B)."""
    t0 = time.perf_counter()
    ttft = None
    seen = 0
    with requests.post(f"{addr}/chat/stream",
                       json={"prompt": prompt,
                             "max_new_tokens": tokens},
                       stream=True, timeout=300) as r:
        if r.status_code != 200:
            return None, 0
        for line in r.iter_lines():
            if not line.startswith(b"data: "):
                continue
            body = line[len(b"data: "):]
            if body == b"[DONE]":
                break
            ev = json.loads(body)
            if "token" in ev:
                if ttft is None:
                    ttft = time.perf_counter() - t0
                seen += 1
    return ttft, seen


def _autoscale_bench_main() -> None:
    """`python bench.py --autoscale-bench`: run the bursty multi-tenant
    scenario in a fresh child and merge an `autoscale` block into
    SERVE_BENCH.json."""
    try:
        proc = _spawn("autoscale")
        result = _extract_json_line(proc.stdout)
        if proc.returncode != 0 or result is None:
            result = {"error": (proc.stderr or "").strip()[-400:]}
    except Exception:
        result = {"error": traceback.format_exc(limit=3)}
    print(json.dumps(result))
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "SERVE_BENCH.json")
    try:
        with open(path) as f:
            ledger = json.load(f)
    except Exception:
        ledger = {"metric": "serve_gen_ttft_ms_p50", "detail": {}}
    ledger["autoscale"] = result
    try:
        with open(path, "w") as f:
            json.dump(ledger, f)
    except OSError:
        pass


def _run_rl_measurement() -> dict:
    """PPO env-steps/s on the local device mesh (BASELINE north star #3:
    100k env-steps/s).  Uses DDPPO — every device a learner, pmean grad
    sync — so the number scales with the mesh instead of one chip."""
    import jax

    from ray_tpu.rl import CartPole, DDPPOConfig

    n = len(jax.devices())
    algo = DDPPOConfig(env=CartPole, num_envs=64, rollout_length=128,
                       num_learners=n, lr=1e-3, seed=0).build()
    algo.train()                      # compile + warmup
    t0 = time.perf_counter()
    steps = 0
    iters = 0
    while time.perf_counter() - t0 < 10.0 or iters < 3:
        res = algo.train()
        steps += res["env_steps_this_iter"]
        iters += 1
    dt = time.perf_counter() - t0
    rate = steps / dt
    return {
        "metric": "ppo_env_steps_per_s", "value": round(rate, 1),
        "unit": "env_steps/s", "vs_baseline": round(rate / 100_000, 4),
        "detail": {"algo": "DDPPO", "num_learners": n, "iters": iters,
                   "backend": jax.default_backend(),
                   "episode_reward_mean":
                       round(res["episode_reward_mean"], 1)},
    }


_CPU_MODES = ("serve", "autoscale", "rl")


def _child_main(mode: str) -> None:
    """Run one measurement in this (fresh) process; `_spawn` has already
    put its platform into the environment."""
    body = {"rl": _run_rl_measurement, "serve": _run_serve_measurement,
            "autoscale": _run_autoscale_bench, "tpu": _run_measurement}[mode]
    print(json.dumps(body()))


def _spawn(mode: str) -> "subprocess.CompletedProcess":
    """Start the child that runs ``mode``.  This parent stays off JAX: a
    parent that has touched it holds the chip, and the child then cannot."""
    env = dict(os.environ)
    env[_CHILD_FLAG] = mode
    env["JAX_PLATFORMS"] = "cpu" if mode in _CPU_MODES else "tpu"
    if mode == "rl":  # 8-device host mesh
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                            " --xla_force_host_platform_device_count=8")
    return subprocess.run(
        [sys.executable, os.path.abspath(__file__)], env=env,
        cwd=os.path.dirname(os.path.abspath(__file__)),
        capture_output=True, text=True,
        timeout=_TPU_ATTEMPT_TIMEOUT if mode == "tpu" else 1800)


def _extract_json_line(out: str):
    for line in reversed(out.strip().splitlines()):
        line = line.strip()
        if line.startswith("{"):
            try:
                return json.loads(line)
            except json.JSONDecodeError:
                continue
    return None


def _rl_main() -> None:
    """`python bench.py --rl`: PPO env-steps/s on an 8-device CPU mesh
    (the TPU headline stays the default mode; this is north star #3)."""
    try:
        proc = _spawn("rl")
        result = _extract_json_line(proc.stdout)
        if proc.returncode == 0 and result is not None:
            print(json.dumps(result))
            return
        err = proc.stderr.strip()[-300:]
    except Exception:
        err = traceback.format_exc(limit=2)
    print(json.dumps({
        "metric": "ppo_env_steps_per_s", "value": 0.0,
        "unit": "env_steps/s", "vs_baseline": 0.0,
        "detail": {"error": err}}))


def _serve_main() -> None:
    """`python bench.py --serve`: generation TTFT/decode through the
    full serving path (north star #5); also records the result to
    SERVE_BENCH.json for the round ledger."""
    try:
        proc = _spawn("serve")
        result = _extract_json_line(proc.stdout)
        if proc.returncode == 0 and result is not None:
            # the measurement is the product; the ledger write is
            # best-effort and must never sink it
            print(json.dumps(result))
            try:
                with open(os.path.join(os.path.dirname(
                        os.path.abspath(__file__)), "SERVE_BENCH.json"),
                        "w") as f:
                    json.dump(result, f)
            except OSError:
                pass
            return
        err = proc.stderr.strip()[-300:]
    except Exception:
        err = traceback.format_exc(limit=2)
    print(json.dumps({
        "metric": "serve_gen_ttft_ms_p50", "value": 0.0,
        "unit": "ms", "vs_baseline": 0.0,
        "detail": {"error": err}}))


def _run_serve_breakdown() -> dict:
    """`--serve-breakdown`: streamed generation through the FULL path
    (HTTP proxy → router → replica continuous-batching engine) on the
    CPU harness, then reduce the data-plane flight instruments to the
    per-phase attribution table (`state.serve_breakdown`).  The product
    is the COVERAGE number: attributed phase seconds (queue, admission,
    prefill, decode_dispatch, stream_drain) over client-measured
    seconds (TTFT + ITL sums) — >= 0.9 means the instruments explain at
    least 90% of what streaming clients actually waited."""
    import ray_tpu
    from ray_tpu import serve, state

    ray_tpu.init(num_cpus=4)
    serve.start()

    @serve.deployment(max_concurrent_queries=8)
    class Generator:
        def __init__(self):
            import jax.numpy as jnp

            from ray_tpu.models import TransformerConfig
            from ray_tpu.serve.decode_session import DecodeSessionCore
            self.core = DecodeSessionCore(
                TransformerConfig.tiny(max_seq_len=256,
                                       dtype=jnp.float32), max_len=256)

        def __call__(self, req):
            return self.core.handle(req)

    import requests
    serve.run(Generator.bind(), name="generate")
    addr = serve.api.http_address()
    http = requests.Session()
    prompt_len, max_new, n_sessions = 48, 24, 12

    def stream_one(i: int) -> int:
        prompt = [(13 * i + j) % 250 for j in range(prompt_len)]
        n = 0
        with http.post(f"{addr}/generate/stream",
                       json={"prompt": prompt,
                             "max_new_tokens": max_new,
                             "tenant": f"bench-{i % 3}"},
                       stream=True, timeout=180) as r:
            r.raise_for_status()
            for line in r.iter_lines():
                if line.startswith(b"data: ") and b"token" in line:
                    n += 1
        return n

    stream_one(0)        # warmup: compiles the chunk + decode programs
    total = sum(stream_one(i) for i in range(1, n_sessions + 1))
    time.sleep(1.5)      # final engine push (0.5s cadence) + fold
    table = state.serve_breakdown()
    serve.shutdown()
    ray_tpu.shutdown()
    dep = (table.get("deployments") or {}).get("generate") or {}
    cov = dep.get("coverage") or 0.0
    return {
        "metric": "serve_breakdown_coverage",
        "value": round(cov, 4),
        "unit": "fraction_of_client_measured_serve_time",
        "vs_baseline": round(cov / 0.9, 4),   # 0.9 is the floor
        "detail": {"sessions": n_sessions,
                   "tokens_streamed": total,
                   "phases": table.get("phases"),
                   "deployments": table.get("deployments"),
                   "note": "coverage = attributed phase seconds / "
                           "(TTFT sum + ITL sum) measured at the "
                           "proxy; >= 0.9 is the acceptance bar"},
    }


def _serve_breakdown_main() -> None:
    """`python bench.py --serve-breakdown` (`make serve-breakdown`):
    run the attribution measurement inline on the CPU backend and
    write the table into SERVE_BENCH.json's top-level ``breakdown``
    block (the headline serve record stays the `--serve` run)."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    try:
        result = _run_serve_breakdown()
    except Exception:
        result = {"metric": "serve_breakdown_coverage", "value": 0.0,
                  "unit": "fraction_of_client_measured_serve_time",
                  "vs_baseline": 0.0,
                  "detail": {"error": traceback.format_exc(limit=3)}}
    print(json.dumps(result))
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "SERVE_BENCH.json")
    try:
        with open(path) as f:
            ledger = json.load(f)
    except Exception:
        ledger = {"metric": "serve_gen_ttft_ms_p50", "detail": {}}
    ledger["breakdown"] = {
        "captured_at": time.strftime("%Y-%m-%dT%H:%M:%S"), **result}
    try:
        with open(path, "w") as f:
            json.dump(ledger, f, indent=1)
    except OSError:
        pass


def _attr_main() -> None:
    """`python bench.py --attr`: scripted control-plane wave (task burst
    + actor burst), then append the per-RPC attribution table — where
    controller/nodelet handler time went, WAL append/fsync cost, loop
    lag, scheduler wave stats — to the SCALE_r06 ledger.  This is the
    'before' snapshot ROADMAP item 4 demands: the same table re-run
    after the batching/sharding work proves where the serialization
    points moved."""
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import ray_tpu
    from ray_tpu import state

    n_tasks = int(os.environ.get("RAY_TPU_ATTR_TASKS", "20000"))
    n_actors = int(os.environ.get("RAY_TPU_ATTR_ACTORS", "200"))
    ray_tpu.init(num_cpus=8, object_store_memory=256 * 1024 * 1024)
    try:
        @ray_tpu.remote
        def noop():
            return None

        @ray_tpu.remote
        class Member:
            def ping(self):
                return 1

        ray_tpu.get([noop.remote() for _ in range(500)], timeout=120)
        t0 = time.perf_counter()
        ray_tpu.get([noop.remote() for _ in range(n_tasks)],
                    timeout=900.0)
        task_dt = time.perf_counter() - t0
        t0 = time.perf_counter()
        actors = [Member.remote() for _ in range(n_actors)]
        assert sum(ray_tpu.get([a.ping.remote() for a in actors],
                               timeout=900.0)) == n_actors
        actor_dt = time.perf_counter() - t0
        time.sleep(1.0)   # let history/trace flush ticks settle
        attr = state.rpc_attribution()
        ctl = attr.get("controller") or {}
        result = {
            "wave": {"tasks": n_tasks, "task_rate_per_s":
                     round(n_tasks / task_dt, 1),
                     "actors": n_actors, "actor_rate_per_s":
                     round(n_actors / actor_dt, 1)},
            "controller_ops": (ctl.get("ops") or [])[:15],
            "controller_top3_by_total_s":
                [r["op"] for r in (ctl.get("ops") or [])[:3]],
            "wal": ctl.get("wal"),
            "controller_loop_lag": ctl.get("loop_lag"),
            "nodes": {nid: (a.get("ops") or [])[:10]
                      for nid, a in (attr.get("nodes") or {}).items()},
        }
        for a in actors:
            ray_tpu.kill(a)
    finally:
        ray_tpu.shutdown()
    print(json.dumps({"metric": "control_plane_rpc_attr",
                      "value": result["wave"]["task_rate_per_s"],
                      "unit": "tasks/s", "detail": result}))
    # merge into the SCALE_r06 ledger (best effort; the table printed
    # above is the product)
    try:
        path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            "SCALE_r06.json")
        ledger = {}
        if os.path.exists(path):
            with open(path) as f:
                ledger = json.load(f)
        ledger.setdefault("round", 6)
        ledger.setdefault(
            "what", "control-plane scale round 6 ledger; rpc_attr_before"
            " is the PR-10 per-RPC attribution snapshot taken BEFORE the"
            " item-4 batching/sharding work")
        ledger["rpc_attr_before"] = {
            "captured_at": time.strftime("%Y-%m-%dT%H:%M:%S"),
            **result}
        with open(path, "w") as f:
            json.dump(ledger, f, indent=1)
    except OSError:
        pass


def main() -> None:
    mode = os.environ.get(_CHILD_FLAG)
    if mode:
        _child_main(mode)
        return
    if "--rl" in sys.argv:
        _rl_main()
        return
    if "--serve" in sys.argv:
        _serve_main()
        return
    if "--spec-bench" in sys.argv:
        _spec_bench_main()
        return
    if "--autoscale-bench" in sys.argv:
        _autoscale_bench_main()
        return
    if "--serve-breakdown" in sys.argv:
        _serve_breakdown_main()
        return
    if "--attr" in sys.argv:
        _attr_main()
        return

    proc = _spawn("tpu")
    result = _extract_json_line(proc.stdout)
    if proc.returncode != 0 or result is None:
        sys.stderr.write(proc.stderr[-4000:])
        raise SystemExit(
            f"bench.py: the TPU child failed (exit code {proc.returncode})")
    print(json.dumps(result))


if __name__ == "__main__":
    main()
