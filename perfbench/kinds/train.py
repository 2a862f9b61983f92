"""Traffic kind ``train``: `JaxTrainer(...).fit()` with one worker that holds
the cell's chips, optimizer steps for the length of the window.

The worker's loop (`train_loop`) is the benchmark's: it makes weights and
token batches from the seed, compiles the program's `make_train_step`,
warms it up, times whole steps that end in `block_until_ready`, and after
the window compares the program with the plain reference.  The parent
(`run`) only starts it and reads its one report.
"""

from __future__ import annotations

import collections
import time
from typing import Any, Dict


# ------------------------------------------------------------ worker side

def _setup(spec: Dict[str, Any]):
    """Model configuration, mesh, step function and a maker of fresh state
    from a seed.  Returns a dict of what the loop needs."""
    import jax
    import optax

    from perfbench import manifest, weights
    from ray_tpu.models import init_params, make_train_step
    c, t = spec["config"], spec["traffic"]
    model = manifest.family_of(c).model
    cfg = model.model_config(c, "train", attention_impl="auto")
    o = t["optimizer"]
    opt = optax.adamw(o["lr"], weight_decay=o["weight_decay"])
    mesh = None
    shardings = batch_sh = None
    if t.get("mesh"):
        from ray_tpu.air import session
        from ray_tpu.parallel import (FSDP_TP_RULES, batch_sharding,
                                      pytree_shardings)
        mesh = session.get_mesh()
        axes = {}

        def note(key):
            p, axes["axes"] = init_params(key, cfg)
            return p

        jax.eval_shape(note, jax.random.PRNGKey(0))
        shardings = pytree_shardings(axes["axes"], mesh, FSDP_TP_RULES)
        batch_sh = batch_sharding(mesh, FSDP_TP_RULES)
    dtype = model.param_dtype(c, "train")
    make_w = jax.jit(lambda key: model.make(key, c, dtype),
                     out_shardings=shardings)
    n_b, per_step, seq = (t["distinct_batches"], t["sequences_per_step"],
                          t["seq_len"])

    def fresh(seed: int):
        key = weights.key_of(seed)
        params = make_w(key)
        # eager under a mesh: zeros_like keeps each parameter's sharding,
        # a jitted init reads only shapes and lands on one device
        opt_state = opt.init(params) if mesh is not None \
            else jax.jit(opt.init)(params)
        toks = model.tokens(jax.random.fold_in(key, 1),
                            (n_b, per_step, seq), c)
        batches = [{"tokens": (jax.device_put(toks[i], batch_sh)
                               if batch_sh is not None else toks[i])}
                   for i in range(n_b)]
        jax.block_until_ready((params, opt_state, batches))
        return params, opt_state, batches

    step = jax.jit(
        make_train_step(cfg, opt,
                        accum_steps=per_step // t["micro_batch"]),
        donate_argnums=(0, 1))
    return {"cfg": cfg, "mesh": mesh, "fresh": fresh, "step": step,
            "make_w": make_w, "model": model}


def _under(mesh):
    import contextlib

    import jax
    return jax.set_mesh(mesh) if mesh is not None \
        else contextlib.nullcontext()


def _compare(spec: Dict[str, Any], env: Dict[str, Any], seed: int,
             step_loss: float, precision: str = "float32",
             control: bool = False) -> Dict[str, float]:
    """The numbers of ``correct`` for one seed.  Weights and tokens are
    made again from the seed (the trained ones are the program's).

    grad_err        relative error of the whole gradient of the program's
                    `lm_loss` (bf16, flash kernel, remat) against the
                    reference's, on the first ``sample_sequences`` of the
                    first batch;
    step_loss_err   the loss the compiled step reported for its first step
                    (32 sequences through the micro-batch scan) against the
                    reference's loss on that batch.
    With ``control`` the reference in fp8 stands in the program's place.
    """
    import functools

    import jax

    from perfbench import reference, weights
    from ray_tpu.models import lm_loss
    c, t, model = spec["config"], spec["traffic"], env["model"]
    key = weights.key_of(seed)
    params = env["make_w"](key)
    toks = model.tokens(jax.random.fold_in(key, 1),
                        (t["distinct_batches"], t["sequences_per_step"],
                         t["seq_len"]), c)[0]
    n = t["check"]["sample_sequences"]
    sample = toks[:n]
    ref = jax.jit(functools.partial(model.loss_and_grad, c=c))
    ref_loss = jax.jit(functools.partial(model.loss, c=c))
    if control:
        got = jax.jit(functools.partial(model.loss_and_grad, c=c,
                                        precision="fp8"))
        got_loss = jax.jit(functools.partial(model.loss, c=c,
                                             precision="fp8"))
    else:
        grad = jax.jit(jax.value_and_grad(
            functools.partial(lm_loss, cfg=env["cfg"])))
        got = lambda p, x: grad(p, {"tokens": x})      # noqa: E731
    with _under(env["mesh"]):
        _, g_ref = ref(params, sample)
        _, g_got = got(params, sample)
        grad_err = float(reference.tree_rel_error(g_got, g_ref))
        del g_got, g_ref
        # the whole first batch, a micro-batch at a time
        mb = t["micro_batch"]
        parts = [toks[i:i + mb] for i in range(0, toks.shape[0], mb)]
        want = sum(float(ref_loss(params, x)) for x in parts) / len(parts)
        if control:
            step_loss = sum(float(got_loss(params, x))
                            for x in parts) / len(parts)
        step_loss_err = abs(step_loss - want)
    return {"grad_err": grad_err, "step_loss_err": step_loss_err}


def train_loop(spec: Dict[str, Any]) -> None:
    """Runs in the worker that holds the chips."""
    stamps = {"worker_ready": time.time()}
    import jax

    from perfbench import chipside
    from ray_tpu.air import session
    chipside.configure_jax()
    t = spec["traffic"]
    env = _setup(spec)
    if spec.get("check_seeds"):
        session.report(_check_many(spec, env))
        return
    params, opt_state, batches = env["fresh"](spec["seed"])
    stamps["weights"] = time.time()
    tokens_per_step = t["sequences_per_step"] * t["seq_len"]
    tracer = chipside.Tracer(spec.get("trace_dir"))
    with _under(env["mesh"]):
        compiled = env["step"].lower(params, opt_state, batches[0]).compile()
        first = None
        for i in range(t["warmup_steps"]):
            params, opt_state, m = compiled(params, opt_state,
                                            batches[i % len(batches)])
            jax.block_until_ready(m)
            if first is None:
                first = float(m["loss"])
        jax.block_until_ready((params, opt_state))
        stamps["warm"] = time.time()
        compiles_before = chipside.compiles()
        # ---- the window: whole steps until --seconds have passed.  The
        # loop keeps ``steps_in_flight`` steps dispatched and blocks on the
        # oldest, so the chip has its next step queued while the host is
        # late (a stall of the host shorter than the queued steps costs
        # the chip nothing); with 1 every step is dispatched and awaited.
        ahead = int(t.get("steps_in_flight", 1))
        ends, losses, pending = [], [], collections.deque()

        def finish_oldest() -> float:
            m = pending.popleft()
            jax.block_until_ready(m)
            ends.append(time.time())
            losses.append(m["loss"])
            return ends[-1]

        stamps["open"] = t_open = time.time()
        sent, trace_until = 0, None
        while True:
            # a traced run empties the queue before the profiler starts
            # and before it stops: the trace holds whole steps only
            if tracer.dir and trace_until is None and len(ends) >= 1:
                while pending:
                    finish_oldest()
                tracer.start()
                trace_until = len(ends) + t["trace_steps"]
            with chipside.annotate("train:step"):
                with chipside.annotate("train:dispatch"):
                    while len(pending) < ahead:
                        params, opt_state, m = compiled(
                            params, opt_state, batches[sent % len(batches)])
                        pending.append(m)
                        sent += 1
                with chipside.annotate("train:block"):
                    now = finish_oldest()
            if trace_until is not None and len(ends) >= trace_until:
                while pending:
                    now = finish_oldest()
                tracer.stop()
                trace_until = float("inf")
            if now - t_open >= spec["seconds"]:
                break
        tracer.stop()
        stamps["close"] = ends[-1]
        # steps still queued at the close belong to no window
        jax.block_until_ready((list(pending), params, opt_state))
    compiles_in_window = chipside.compiles() - compiles_before
    losses = [float(x) for x in losses]
    who = chipside.report(chipside.program_bytes(compiled))
    has_kernel = "tpu_custom_call" in compiled.as_text()
    # ---- after the window: free the trained state, compare
    for leaf in jax.tree_util.tree_leaves((params, opt_state)):
        leaf.delete()
    del params, opt_state, compiled
    numbers = _compare(spec, env, spec["seed"], first)
    finite = all(x == x and abs(x) != float("inf") for x in losses)
    session.report({
        "stamps": stamps, "worker": who, "step_ends": ends,
        "losses": losses, "first_step_loss": first, "losses_finite": finite,
        "tokens_per_step": tokens_per_step,
        "compiles_in_window": compiles_in_window, "has_kernel": has_kernel,
        "numbers": numbers, "trace": tracer.result(),
        "mesh": ({k: int(v) for k, v in env["mesh"].shape.items() if v > 1}
                 if env["mesh"] is not None else None)})


def _check_many(spec: Dict[str, Any], env: Dict[str, Any]) -> Dict[str, Any]:
    """tools/outputs_check.py: one process, one set-up, many seeds: the
    program's numbers on each, and the control's on the first few."""
    import jax

    from perfbench import chipside
    rows = []
    with _under(env["mesh"]):
        compiled = None
        for n, seed in enumerate(spec["check_seeds"]):
            params, opt_state, batches = env["fresh"](seed)
            if compiled is None:
                compiled = env["step"].lower(
                    params, opt_state, batches[0]).compile()
            params, opt_state, m = compiled(params, opt_state, batches[0])
            first = float(m["loss"])
            for leaf in jax.tree_util.tree_leaves(
                    (params, opt_state, batches)):
                leaf.delete()
            row = {"seed": seed,
                   "program": _compare(spec, env, seed, first)}
            if n < spec["control_seeds"]:
                row["control"] = _compare(spec, env, seed, first,
                                          control=True)
            rows.append(row)
    return {"rows": rows, "worker": chipside.report()}


# ------------------------------------------------------------ parent side

def run(ctx) -> Dict[str, Any]:
    from ray_tpu.air.config import RunConfig, ScalingConfig
    from ray_tpu.train import JaxTrainer
    from ray_tpu.train.backend import SpmdConfig
    t = ctx.traffic
    spec = {"config": ctx.config, "traffic": t, "seed": ctx.seed,
            "seconds": ctx.seconds, "trace_dir": ctx.trace_dir,
            **ctx.extra}
    scaling = ScalingConfig(num_workers=1,
                            resources_per_worker={"TPU": float(ctx.chips)})
    result = JaxTrainer(
        train_loop, train_loop_config=spec, scaling_config=scaling,
        run_config=RunConfig(name="perfbench",
                             storage_path=ctx.scratch("train_results")),
        backend_config=SpmdConfig(mesh=t.get("mesh"))).fit()
    if result.error is not None:
        raise result.error
    m = result.metrics
    if "rows" in m:                       # outputs check
        return m
    steps = len(m["step_ends"])
    return {
        "worker": m["worker"], "stamps": m["stamps"],
        "attempted": steps, "failed": 0 if m["losses_finite"] else steps,
        "numbers": m["numbers"],
        "sanity": {"losses_finite": m["losses_finite"],
                   "loss_fell": m["losses"][-1] < m["first_step_loss"],
                   # the micro-batch scan weighs its parts as the plain
                   # mean over the batch does; no precision test (fp8
                   # moves the mean loss by less than bf16's own spread)
                   "step_loss_near_reference":
                       m["numbers"]["step_loss_err"] < 0.01,
                   "has_kernel": m["has_kernel"]},
        "train": m, "trace": m["trace"],
        "counters": {"compiles_in_window": m["compiles_in_window"]}}
