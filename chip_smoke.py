#!/usr/bin/env python3
"""Prove that the system starts on the chip: gpt2-medium trained and served
through the entry points a user calls, one process per chip.

    python chip_smoke.py              # one chip: runtime, train, hand-over,
                                      #           serve, shutdown
    python chip_smoke.py --chips 4    # four chips: the sharded train step and
                                      #           the one-device run it is
                                      #           compared with, nothing else

This process never imports JAX: the chip belongs to the worker that
reserved it, and a parent that had touched JAX would hold it.  Every phase
prints one JSON line; a phase whose check fails ends the run at once with a
non-zero exit code.  The last line, printed only when every phase passed, is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

with the values the workers that held the chip reported.  Without a chip
(or with ``JAX_PLATFORMS=cpu``) the runtime phase fails: nothing falls back
to the CPU.  The model is at its full published width and depth; the
weights and the tokens are random, made from ``--seed``.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import threading
import time
import urllib.request

MODEL = dict(size="medium", seq=1024)      # TransformerConfig.gpt2("medium")
TRAIN = dict(batch=4, steps=5, lr=1e-3, first_loss_tol=0.5)
SERVE = dict(prompt_lens=(64, 160, 301, 512), new_tokens=32, max_len=1024,
             logit_gap=0.1)
SHARDED = dict(mesh="fsdp=2,tp=2", batch=4, steps=5, lr=1e-3, loss_tol=5e-2)


class SmokeFailure(SystemExit):
    """A check did not hold; carries the message as the exit status, so the
    interpreter prints it to stderr and exits with code 1."""


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


class Checks:
    """A real run fails at the first check that does not hold.  The CPU
    rehearsal (tests/test_chip_smoke.py) cannot pass the checks that need
    the device, so it alone defers those to the end of the run — and then
    fails on them all the same."""

    def __init__(self, defer_device_checks: bool = False):
        self._defer = defer_device_checks
        self.deferred: list = []

    def require(self, ok: bool, what: str, *, needs_device: bool = False):
        if ok:
            return
        if needs_device and self._defer:
            self.deferred.append(what)
            return
        raise SmokeFailure(f"chip_smoke: FAILED: {what}")


# ----------------------------------------------------------- worker side
# Everything below this line up to "parent side" runs in the worker that
# holds the chip, never in this process.

def _model_config(spec: dict, **overrides):
    import jax.numpy as jnp

    from ray_tpu.models import TransformerConfig
    if spec.get("tiny"):                        # the CPU rehearsal only
        return TransformerConfig.tiny(
            max_seq_len=spec["seq"], dtype=jnp.float32, **overrides)
    return TransformerConfig.gpt2(spec["size"], max_seq_len=spec["seq"],
                                  dtype=jnp.bfloat16, **overrides)


def _worker_report() -> dict:
    """Who this worker is: its process, its platform switch, the devices
    JAX gives it, and which TPU tokens of the node are still free while it
    runs (its own must not be among them)."""
    import jax

    import ray_tpu
    d = jax.devices()
    free = ray_tpu.available_resources()
    return {"pid": os.getpid(),
            "jax_platforms": os.environ.get("JAX_PLATFORMS"),
            "compile_cache_dir": jax.config.jax_compilation_cache_dir,
            "device": {"platform": d[0].platform, "kind": d[0].device_kind,
                       "count": len(d)},
            "tpu_tokens_free": {
                k: free.get(k, 0.0)
                for k in ray_tpu.cluster_resources() if k.startswith("TPU")}}


def _compile_and_run(step, params, opt_state, data, steps: int):
    """Compile ``step`` ahead of time, then run it ``steps`` times on the
    same batch.  Each step is timed twice: dispatch -> block_until_ready,
    and dispatch -> a scalar read back to the host."""
    import jax
    t0 = time.perf_counter()
    compiled = step.lower(params, opt_state, data).compile()
    compile_s = time.perf_counter() - t0
    rows = []
    for _ in range(steps):
        t0 = time.perf_counter()
        params, opt_state, m = compiled(params, opt_state, data)
        jax.block_until_ready((params, opt_state, m))
        blocked_s = time.perf_counter() - t0
        loss, gnorm = float(m["loss"]), float(m["grad_norm"])
        readback_s = time.perf_counter() - t0
        rows.append({"loss": loss, "grad_norm": gnorm,
                     "blocked_s": blocked_s, "readback_s": readback_s})
    return compiled, compile_s, rows, (params, opt_state)


def train_loop(spec: dict) -> None:
    """One chip: fp32 parameters and AdamW, bf16 compute, five steps."""
    import jax
    import optax

    from ray_tpu.air import session
    from ray_tpu.models import init_params, make_train_step
    cfg = _model_config(spec["model"], attention_impl="auto")
    t0 = time.perf_counter()
    # one program each, not one per parameter: a cold TPU process pays
    # about a second of compilation for every distinct eager op
    params = jax.jit(lambda key: init_params(key, cfg)[0])(
        jax.random.PRNGKey(spec["seed"]))
    opt = optax.adamw(spec["lr"], weight_decay=0.1)
    opt_state = jax.jit(opt.init)(params)
    data = {"tokens": jax.random.randint(
        jax.random.PRNGKey(spec["seed"] + 1),
        (spec["batch"], spec["model"]["seq"]), 0, cfg.vocab_size)}
    jax.block_until_ready((params, opt_state, data))
    init_s = time.perf_counter() - t0
    step = jax.jit(make_train_step(cfg, opt), donate_argnums=(0, 1))
    compiled, compile_s, rows, _ = _compile_and_run(
        step, params, opt_state, data, spec["steps"])
    stats = jax.devices()[0].memory_stats() or {}
    session.report({
        **_worker_report(), "vocab_size": cfg.vocab_size, "init_s": init_s,
        "compile_s": compile_s, "steps": rows,
        "has_kernel": "tpu_custom_call" in compiled.as_text(),
        "peak_bytes_in_use": stats.get("peak_bytes_in_use")})


def sharded_loop(spec: dict) -> None:
    """Four chips in one process: the step under fsdp x tp, then the same
    seed, batch and steps on a one-device mesh of the same process."""
    import gc

    import jax
    import optax

    from ray_tpu.air import session
    from ray_tpu.models import init_params, make_train_step
    from ray_tpu.parallel import (FSDP_TP_RULES, MeshSpec, batch_sharding,
                                  create_mesh, pytree_shardings)
    cfg = _model_config(spec["model"], attention_impl="auto")
    opt = optax.adamw(spec["lr"], weight_decay=0.1)

    def init(key):
        return init_params(key, cfg)[0]

    logical_axes = {}

    def note_axes(key):
        params, logical_axes["axes"] = init_params(key, cfg)
        return params

    jax.eval_shape(note_axes, jax.random.PRNGKey(0))

    def run(mesh):
        # initialised in place: no unsharded copy ever sits on one device
        placed = jax.jit(init, out_shardings=pytree_shardings(
            logical_axes["axes"], mesh, FSDP_TP_RULES))(
                jax.random.PRNGKey(spec["seed"]))
        # eager on purpose: zeros_like keeps each parameter's sharding, while
        # a jitted init reads only shapes, so its outputs land on one device
        opt_state = opt.init(placed)
        data = {"tokens": jax.device_put(
            jax.random.randint(jax.random.PRNGKey(spec["seed"] + 1),
                               (spec["batch"], spec["model"]["seq"]), 0,
                               cfg.vocab_size),
            batch_sharding(mesh, FSDP_TP_RULES))}
        jax.block_until_ready((opt_state, data))
        gc.collect()
        largest = max(jax.tree_util.tree_leaves(placed),
                      key=lambda a: a.size)
        placement = {
            "bytes_in_use": [(d.memory_stats() or {}).get("bytes_in_use")
                             for d in mesh.devices.flat],
            # fewest devices any optimizer moment is spread over
            "opt_state_devices": min(
                len(x.sharding.device_set)
                for x in jax.tree_util.tree_leaves(opt_state) if x.ndim),
            "largest_param_shape": list(largest.shape),
            "largest_param_shard_shape":
                list(largest.addressable_shards[0].data.shape),
            "largest_param_devices": sorted(
                {s.device.id for s in largest.addressable_shards})}
        step = jax.jit(make_train_step(cfg, opt), donate_argnums=(0, 1))
        with jax.set_mesh(mesh):
            compiled, compile_s, rows, live = _compile_and_run(
                step, placed, opt_state, data, spec["steps"])
        text = compiled.as_text()
        out = {"mesh": {k: int(v) for k, v in mesh.shape.items() if v > 1},
               "compile_s": compile_s, "steps": rows,
               "placement": placement,
               "has_kernel": "tpu_custom_call" in text,
               "collectives": {op: op in text for op in (
                   "all-gather", "reduce-scatter", "all-reduce")}}
        for leaf in jax.tree_util.tree_leaves((live, data)):
            leaf.delete()
        return out

    sharded = run(session.get_mesh())
    gc.collect()
    single = run(create_mesh(MeshSpec(fsdp=1), devices=jax.devices()[:1]))
    session.report({**_worker_report(), "sharded": sharded,
                    "single": single})


def make_deployment(serve, spec: dict):
    """The served model: bf16 parameters in a `DecodeSessionCore` with the
    continuous-batching engine on, in a replica that reserves the chip."""

    @serve.deployment(name="gpt2", max_concurrent_queries=16,
                      ray_actor_options={"num_tpus": 1})
    class Gpt2:
        def __init__(self, spec):
            import jax
            import jax.numpy as jnp

            from ray_tpu.models import init_params
            from ray_tpu.serve.config import DecodeEngineConfig
            from ray_tpu.serve.decode_session import DecodeSessionCore
            dtype = jnp.float32 if spec["model"].get("tiny") \
                else jnp.bfloat16
            self.cfg = _model_config(spec["model"], attention_impl="auto",
                                     param_dtype=dtype)
            cfg = self.cfg
            self.params = jax.jit(lambda key: init_params(key, cfg)[0])(
                jax.random.PRNGKey(spec["seed"]))
            self.core = DecodeSessionCore(
                self.cfg, max_len=spec["max_len"], params=self.params,
                engine=DecodeEngineConfig(admission_timeout_s=300.0))

        def __call__(self, req):
            op = req.get("op")
            if op == "warmup":
                return self._warmup()
            if op == "report":
                return self._report()
            if op == "verify":
                return self._verify(req["prompts"], req["streams"])
            return self.core.handle(req)

        def _warmup(self):
            """One short session through the engine compiles its programs
            (a full prefill chunk and a padded one: one shape; the decode
            step, the slot insert) before the timed requests arrive."""
            chunk = self.core.engine.ecfg.prefill_chunk_tokens
            t0 = time.perf_counter()
            out = self.core.handle({"op": "start",
                                    "prompt": [1] * (chunk + 3)})
            got = len(out["token"])
            while got < 4:
                more = self.core.handle({"op": "next_chunk",
                                         "sid": out["sid"], "max_tokens": 4})
                if not more.get("tokens"):
                    raise RuntimeError(f"warm-up stream stalled: {more}")
                got += len(more["tokens"])
            self.core.handle({"op": "end", "sid": out["sid"]})
            return {"warmup_s": time.perf_counter() - t0}

        def _report(self):
            import jax
            stats = self.core.engine.stats()
            mem = jax.devices()[0].memory_stats() or {}
            return {
                **_worker_report(),
                "distinct_program_shapes":
                    stats["distinct_program_shapes"],
                "program_shapes": stats["program_shapes"],
                "compile_s": {r["program"]: r["compile_s"]
                              for r in stats["device_profile"]},
                "peak_bytes_in_use": mem.get("peak_bytes_in_use")}

        def _verify(self, prompts, streams):
            """The reference: one plain full-recompute forward of every
            prompt + stream (right-padded to one length; the model is
            causal, so padding cannot reach the positions read), here on
            the replica's device and dtype.  Returns, for each generated
            position, how far the served token's reference logit lies
            under that position's largest, and whether the unbatched
            `generate` produced the same stream."""
            import jax
            import jax.numpy as jnp
            import numpy as np

            from ray_tpu.models import forward, generate
            n_new = len(streams[0])
            longest = max(len(p) for p in prompts) + n_new
            width = min(self.cfg.max_seq_len, 128 * math.ceil(longest / 128))
            tokens = np.zeros((len(prompts), width), np.int32)
            for i, (p, s) in enumerate(zip(prompts, streams)):
                tokens[i, :len(p) + n_new] = list(p) + list(s)
            # logits[pos] predicts the token at pos + 1
            first = np.asarray([len(p) - 1 for p in prompts], np.int32)
            cfg = self.cfg

            @jax.jit
            def gaps(params, tokens, first, served):
                logits = forward(params, tokens, cfg)
                pos = first[:, None] + jnp.arange(n_new)[None, :]
                rows = jnp.take_along_axis(logits, pos[:, :, None], axis=1)
                top = rows.max(axis=-1)
                got = jnp.take_along_axis(rows, served[:, :, None],
                                          axis=-1)[..., 0]
                return top - got

            gap = np.asarray(gaps(self.params, jnp.asarray(tokens),
                                  jnp.asarray(first),
                                  jnp.asarray(streams, jnp.int32)))
            same = []
            for p, s in zip(prompts, streams):
                ref = generate(self.params, jnp.asarray([p], jnp.int32),
                               cfg=cfg, max_new_tokens=n_new)
                same.append(np.asarray(ref)[0].tolist() == list(s))
            return {"gaps": gap.tolist(), "equals_generate": same}

    return Gpt2.bind(spec)


# ----------------------------------------------------------- parent side

def _post(url: str, payload: dict, timeout: float) -> dict:
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    # a status other than 200 raises, and that ends the run
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        if resp.status != 200:
            raise SmokeFailure(f"chip_smoke: FAILED: HTTP {resp.status}")
        return json.loads(resp.read())


def _process_gone(pid: int) -> bool:
    """Reaped, not merely a zombie: the leader of a thread group shows as
    a zombie while its other threads are still exiting, and the chip is
    closed by the last of them.  Its parent can reap it only after that."""
    return not os.path.exists(f"/proc/{pid}")


def _chip_open_in(session_dir: str) -> list:
    """Pids of the run's processes that have a chip's device file open."""
    from ray_tpu.core.node import session_processes
    holders = []
    for pid in session_processes(session_dir):
        try:
            fds = os.listdir(f"/proc/{pid}/fd")
        except OSError:
            continue                              # exited meanwhile
        for fd in fds:
            try:
                target = os.readlink(f"/proc/{pid}/fd/{fd}")
            except OSError:
                continue
            if target.startswith(("/dev/vfio/", "/dev/accel")):
                holders.append(pid)
                break
    return holders


def _prompts(seed: int, lens, vocab: int) -> list:
    import random
    rng = random.Random(seed)
    return [[rng.randrange(vocab) for _ in range(n)] for n in lens]


def _check_worker(checks: Checks, report: dict, chips: int, who: str):
    """The worker held the reservation, was started on the TPU platform
    and nothing else, and JAX gave it the chips."""
    # the node's TPU and, inside a placement group, the bundle's own token
    # (TPU_group_<index>_<pg>); the group's wildcard token stays free
    free = {k: v for k, v in report["tpu_tokens_free"].items()
            if k == "TPU" or len(k.split("_")) == 4}
    checks.require(free and not any(free.values()),
                   f"{who} does not hold the TPU reservation: while it ran "
                   f"the node had {free} free")
    checks.require(report["jax_platforms"] == "tpu",
                   f"{who}'s JAX_PLATFORMS was {report['jax_platforms']!r}",
                   needs_device=True)
    device = report["device"]
    checks.require(device["platform"] == "tpu" and device["count"] == chips,
                   f"{who} ran on {device}, not on {chips} TPU chip(s)",
                   needs_device=True)
    if device["platform"] == "tpu":
        # a chip the peaks table does not name exactly is an error
        from ray_tpu.util.device_profile import peak_flops_of
        peak_flops_of(device["kind"])


def _check_losses(checks: Checks, rows: list, vocab: int, tol: float,
                  who: str):
    losses = [r["loss"] for r in rows]
    checks.require(all(math.isfinite(r["loss"])
                       and math.isfinite(r["grad_norm"]) for r in rows),
                   f"{who}: a loss or gradient norm is not finite: {rows}")
    checks.require(abs(losses[0] - math.log(vocab)) < tol,
                   f"{who}: first loss {losses[0]:.3f} is not within {tol} "
                   f"of ln {vocab} = {math.log(vocab):.3f}")
    checks.require(losses[-1] < losses[0],
                   f"{who}: loss did not fall on a repeated batch: {losses}")


def phase_runtime(checks: Checks, chips: int, init_kwargs: dict) -> dict:
    import ray_tpu
    t0 = time.perf_counter()
    ray_tpu.init(system_config={"serve_request_timeout_s": 900.0},
                 **init_kwargs)
    total = ray_tpu.cluster_resources()
    kinds = [k for k in total if k.startswith("accelerator_type:")]
    checks.require(total.get("TPU") == float(chips) and len(kinds) == 1,
                   f"the node advertises {total}; expected TPU: {chips} "
                   "and one accelerator_type")
    emit("runtime", seconds=time.perf_counter() - t0, resources=total)
    return total


def phase_train(checks: Checks, seed: int, model: dict, train: dict) -> dict:
    from ray_tpu.air.config import ScalingConfig
    from ray_tpu.train import JaxTrainer
    t0 = time.perf_counter()
    result = JaxTrainer(
        train_loop,
        train_loop_config=dict(train, seed=seed, model=model),
        scaling_config=ScalingConfig(num_workers=1, use_tpu=True)).fit()
    if result.error is not None:
        raise result.error
    m = result.metrics
    _check_worker(checks, m, 1, "the training worker")
    _check_losses(checks, m["steps"], m["vocab_size"],
                  train["first_loss_tol"], "train")
    checks.require(m["has_kernel"],
                   "the compiled train step holds no tpu_custom_call: "
                   "attention gave way to the reference", needs_device=True)
    emit("train", seconds=time.perf_counter() - t0, **m)
    return m


def phase_handover(checks: Checks, pid: int, chips: int) -> None:
    """The next claimant must find the chip free: wait for the training
    worker's PROCESS to be gone, not for its actor's state, and for the
    node to have taken the reservation back (it does so only after it has
    seen the process exit)."""
    import ray_tpu
    t0 = time.perf_counter()
    deadline = t0 + 60.0
    while not _process_gone(pid):
        checks.require(time.perf_counter() < deadline,
                       f"training worker {pid} still alive after 60 s")
        time.sleep(0.05)
    gone_s = time.perf_counter() - t0
    while ray_tpu.available_resources().get("TPU") != float(chips):
        checks.require(time.perf_counter() < deadline,
                       "the node did not take the TPU reservation back")
        time.sleep(0.05)
    emit("handover", seconds=time.perf_counter() - t0,
         process_gone_s=gone_s, pid=pid)


def phase_serve(checks: Checks, seed: int, model: dict, serve_spec: dict,
                vocab: int, train_pid: int) -> dict:
    from ray_tpu import serve
    t0 = time.perf_counter()
    serve.run(make_deployment(
        serve, dict(seed=seed, model=model, max_len=serve_spec["max_len"])))
    url = serve.api.http_address() + "/gpt2"
    warm = _post(url, {"op": "warmup"}, 900)
    ready_s = time.perf_counter() - t0

    prompts = _prompts(seed + 2, serve_spec["prompt_lens"], vocab)
    n_new = serve_spec["new_tokens"]
    streams: list = [None] * len(prompts)
    errors: list = []

    def stream(i: int) -> None:
        try:
            out = _post(url, {"op": "start", "prompt": prompts[i]}, 600)
            toks = list(out["token"])
            while len(toks) < n_new:
                more = _post(url, {"op": "next_chunk", "sid": out["sid"],
                                   "max_tokens": n_new - len(toks)}, 600)
                toks += more["tokens"]
                if more.get("done") and len(toks) < n_new:
                    break
            _post(url, {"op": "end", "sid": out["sid"]}, 60)
            streams[i] = toks
        except BaseException as e:      # re-raised on the main thread
            errors.append(e)

    t1 = time.perf_counter()
    threads = [threading.Thread(target=stream, args=(i,))
               for i in range(len(prompts))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if errors:
        raise errors[0]
    requests_s = time.perf_counter() - t1
    checks.require(all(s is not None and len(s) == n_new for s in streams),
                   f"streams are not {n_new} tokens each: "
                   f"{[len(s or ()) for s in streams]}")

    report = _post(url, {"op": "report"}, 60)
    checks.require(report["pid"] != train_pid,
                   "the replica runs in the training worker's process")
    _check_worker(checks, report, 1, "the replica")
    from ray_tpu import api
    holders = _chip_open_in(api._local_cluster.session_dir)
    checks.require(holders == [report["pid"]],
                   f"while the replica (pid {report['pid']}) is up, the "
                   f"chip's device files are open in {holders}",
                   needs_device=True)

    t2 = time.perf_counter()
    verdict = _post(url, {"op": "verify", "prompts": prompts,
                          "streams": streams}, 900)
    worst = max(max(row) for row in verdict["gaps"])
    emit("serve_reference", seconds=time.perf_counter() - t2,
         worst_logit_gap=worst,
         worst_gap_per_stream=[max(row) for row in verdict["gaps"]],
         equals_generate_share=sum(verdict["equals_generate"])
         / len(prompts))
    checks.require(worst <= serve_spec["logit_gap"],
                   f"a served token lies {worst:.4f} under the reference "
                   f"forward's largest logit (limit "
                   f"{serve_spec['logit_gap']})")
    emit("serve", seconds=time.perf_counter() - t0, ready_s=ready_s,
         warmup_s=warm["warmup_s"], requests_s=requests_s,
         prompt_lens=[len(p) for p in prompts], **report)
    return report


def phase_shutdown(checks: Checks) -> None:
    import ray_tpu
    from ray_tpu import api, serve, state
    from ray_tpu.core.node import session_processes
    # how far the host's own daemons fell behind during the run: the node
    # is declared dead after node_death_timeout_s of heartbeat silence
    attr = state.rpc_attribution()
    emit("control_plane",
         controller_loop_lag_max_ms=attr["controller"]["loop_lag"]["max_ms"],
         controller_wal=attr["controller"].get("wal"),
         nodelet_loop_lag_max_ms=[n["loop_lag"]["max_ms"]
                                  for n in attr["nodes"].values()])
    t0 = time.perf_counter()
    session_dir = api._local_cluster.session_dir
    started = len(session_processes(session_dir))
    serve.shutdown()
    ray_tpu.shutdown()
    deadline = time.perf_counter() + 30.0
    while (alive := session_processes(session_dir)) \
            and time.perf_counter() < deadline:
        time.sleep(0.05)
    checks.require(not alive, f"processes outlived shutdown: {alive}")
    emit("shutdown", seconds=time.perf_counter() - t0, processes=started)


def phase_sharded(checks: Checks, seed: int, model: dict,
                  sharded: dict) -> dict:
    from ray_tpu.air.config import ScalingConfig
    from ray_tpu.train import JaxTrainer
    from ray_tpu.train.backend import SpmdConfig
    t0 = time.perf_counter()
    result = JaxTrainer(
        sharded_loop,
        train_loop_config=dict(sharded, seed=seed, model=model),
        scaling_config=ScalingConfig(num_workers=1,
                                     resources_per_worker={"TPU": 4}),
        backend_config=SpmdConfig(mesh=sharded["mesh"])).fit()
    if result.error is not None:
        raise result.error
    m = result.metrics
    _check_worker(checks, m, 4, "the sharded worker")
    four, one = m["sharded"], m["single"]
    la = [r["loss"] for r in four["steps"]]
    lb = [r["loss"] for r in one["steps"]]
    emit("sharded", seconds=time.perf_counter() - t0, **m)
    checks.require(all(math.isfinite(x) for x in la + lb),
                   f"a loss is not finite: {la} {lb}")
    checks.require(
        max(abs(a - b) for a, b in zip(la, lb)) <= sharded["loss_tol"],
        f"four-chip and one-device losses differ by more than "
        f"{sharded['loss_tol']}: {la} vs {lb}")
    coll = four["collectives"]
    checks.require(coll["all-gather"] and (coll["reduce-scatter"]
                                           or coll["all-reduce"]),
                   f"the four-chip step lacks collectives: {coll}",
                   needs_device=True)
    checks.require(four["placement"]["opt_state_devices"] == 4,
                   "an optimizer moment is not sharded like its parameter: "
                   f"{four['placement']}")
    used = four["placement"]["bytes_in_use"]
    checks.require(all(used) and max(used) <= 1.25 * min(used),
                   f"bytes_in_use after placement is uneven: {used}",
                   needs_device=True)
    checks.require(len(four["placement"]["largest_param_devices"]) == 4,
                   f"the largest parameter's shards sit on "
                   f"{four['placement']['largest_param_devices']}",
                   needs_device=True)
    return m


def main(argv=None, rehearsal: dict | None = None) -> int:
    """``rehearsal`` is for tests/test_chip_smoke.py alone: a tiny model
    and a node with a stand-in ``TPU`` token, to walk the phases on the
    CPU.  No option of the command line can make a chip run small."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    rehearsal = rehearsal or {}
    if not rehearsal and os.environ.get("RAY_TPU_PALLAS_INTERPRET"):
        raise SmokeFailure(
            "chip_smoke: RAY_TPU_PALLAS_INTERPRET is a test-only switch; "
            "unset it for a chip run")
    model = rehearsal.get("model", MODEL)
    # pickle this module's functions by value: workers do not import it
    import cloudpickle
    cloudpickle.register_pickle_by_value(sys.modules[__name__])

    checks = Checks(defer_device_checks=bool(rehearsal))
    t0 = time.perf_counter()
    phase_runtime(checks, args.chips, rehearsal.get("init_kwargs", {}))
    if args.chips == 4:
        done = phase_sharded(checks, args.seed, model,
                             rehearsal.get("sharded", SHARDED))
        device = done["device"]
        phase_handover(checks, done["pid"], 4)
    else:
        trained = phase_train(checks, args.seed, model,
                              rehearsal.get("train", TRAIN))
        phase_handover(checks, trained["pid"], 1)
        served = phase_serve(checks, args.seed, model,
                             rehearsal.get("serve", SERVE),
                             trained["vocab_size"], trained["pid"])
        checks.require(served["device"] == trained["device"],
                       f"the two workers saw different devices: "
                       f"{trained['device']} / {served['device']}")
        device = served["device"]
    phase_shutdown(checks)
    emit("total", seconds=time.perf_counter() - t0)
    if checks.deferred:
        raise SmokeFailure("chip_smoke: FAILED (device checks): "
                           + "; ".join(checks.deferred))
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
