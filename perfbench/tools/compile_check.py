"""Compile a cell's main program at its real size for a v5e that is
described and not attached (no chip time): what the chip's compiler refuses,
it refuses here, and `memory_analysis()` says whether the program fits.

    JAX_PLATFORMS=cpu python3 -m perfbench.tools.compile_check <cell> [key=value ...]

``key=value`` overrides a number of the traffic file (``micro_batch=4``).
Nothing runs, so this gives no time and no result; it is never reported as
a chip run.
"""

from __future__ import annotations

import functools
import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(argv) -> int:
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from perfbench import manifest as mf
    jax.config.update("jax_enable_compilation_cache", False)
    m = mf.Manifest()
    cell = m.cell(argv[0])
    c, t = m.config(cell["config"]), m.traffic(cell["traffic"])
    model = mf.family_of(c).model
    for kv in argv[1:]:
        k, v = kv.split("=")
        t[k] = json.loads(v)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    t0 = time.time()
    if t["kind"] == "train":
        import optax

        from ray_tpu.models import init_params, make_train_step
        from ray_tpu.parallel import (FSDP_TP_RULES, MeshSpec,
                                      batch_sharding, create_mesh,
                                      pytree_shardings)
        cfg = model.model_config(c, "train", attention_impl="flash")
        opt = optax.adamw(t["optimizer"]["lr"])
        shapes = jax.eval_shape(
            lambda k: model.make(k, c, jnp.float32), jax.random.PRNGKey(0))
        mesh = None
        if t.get("mesh"):
            mesh = create_mesh(MeshSpec.parse(t["mesh"]),
                               devices=topo.devices)
            axes = {}

            def note(key):
                p, axes["axes"] = init_params(key, cfg)
                return p
            jax.eval_shape(note, jax.random.PRNGKey(0))
            sh = pytree_shardings(axes["axes"], mesh, FSDP_TP_RULES)
            bsh = batch_sharding(mesh, FSDP_TP_RULES)
        else:
            sh = jax.tree_util.tree_map(lambda _: one, shapes)
            bsh = one
        params = jax.tree_util.tree_map(
            lambda s, d: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=d),
            shapes, sh)
        opt_shapes = jax.eval_shape(opt.init, params)
        # moments are sharded like their parameter, counters replicated
        flat_p = {x.shape: x.sharding
                  for x in jax.tree_util.tree_leaves(params)}
        rep = one if mesh is None else jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec())
        opt_state = jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(
                s.shape, s.dtype,
                sharding=flat_p.get(s.shape, rep) if s.ndim else rep),
            opt_shapes)
        batch = {"tokens": jax.ShapeDtypeStruct(
            (t["sequences_per_step"], t["seq_len"]), jnp.int32,
            sharding=bsh)}
        step = jax.jit(make_train_step(
            cfg, opt, accum_steps=t["sequences_per_step"] // t["micro_batch"]),
            donate_argnums=(0, 1))
        ctx = jax.set_mesh(mesh) if mesh is not None \
            else __import__("contextlib").nullcontext()
        with ctx:
            compiled = step.lower(params, opt_state, batch).compile()
    else:
        from ray_tpu.models import decode_step_slots, init_slot_cache
        cfg = model.model_config(c, "serve", attention_impl="flash")
        eng = t["engine"]

        def fused(params, tok, cache, active):
            logits, cache = decode_step_slots(params, tok, cache, active, cfg)
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return jnp.where(active, nxt, tok), cache

        def on_one(tree):
            return jax.tree_util.tree_map(
                lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                               sharding=one), tree)
        params = on_one(jax.eval_shape(
            lambda k: model.make(k, c, jnp.bfloat16),
            jax.random.PRNGKey(0)))
        cache = on_one(jax.eval_shape(functools.partial(
            init_slot_cache, cfg, eng["max_slots"], eng["max_len"])))
        tok = jax.ShapeDtypeStruct((eng["max_slots"],), jnp.int32,
                                   sharding=one)
        act = jax.ShapeDtypeStruct((eng["max_slots"],), jnp.bool_,
                                   sharding=one)
        compiled = jax.jit(fused).lower(params, tok, cache, act).compile()
    text = compiled.as_text()
    ma = compiled.memory_analysis()
    print(json.dumps({
        "cell": cell["name"], "overrides": argv[1:],
        "compile_s": round(time.time() - t0, 1),
        "argument_gb": ma.argument_size_in_bytes / 1e9,
        "output_gb": ma.output_size_in_bytes / 1e9,
        "temp_gb": ma.temp_size_in_bytes / 1e9,
        "alias_gb": ma.alias_size_in_bytes / 1e9,
        "kernels": text.count("tpu_custom_call"),
        "collectives": {k: text.count(k) for k in (
            "all-gather", "reduce-scatter", "all-reduce")}}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
