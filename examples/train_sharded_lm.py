"""Train a sharded transformer with JaxTrainer: placement group ->
worker gang -> jax.distributed mesh -> pjit training loop."""

import jax
import jax.numpy as jnp
import optax

from ray_tpu.air import ScalingConfig, session
from ray_tpu.train import JaxTrainer


def train_loop(config):
    from ray_tpu.models import TransformerConfig, init_params, make_train_step
    from ray_tpu.parallel import FSDP_TP_RULES, batch_sharding, \
        pytree_shardings

    mesh = session.get_mesh()
    cfg = TransformerConfig.tiny(max_seq_len=32,
                                 attention_impl="reference",
                                 dtype=jnp.float32)
    params, axes = init_params(jax.random.PRNGKey(0), cfg)
    # the rules cut every matrix (embed -> fsdp, heads / mlp / vocab -> tp)
    # and leave every vector whole on each chip: a norm's scale or bias cut
    # four ways saves a few KB and costs a blocking gather at every use and
    # a blocking sum of its gradient in every layer (parallel/sharding.py)
    params = jax.device_put(params,
                            pytree_shardings(axes, mesh, FSDP_TP_RULES))
    opt = optax.adamw(1e-3)
    opt_state = opt.init(params)
    # accum_steps: microbatch the compiled step (activation memory at
    # batch/accum; Adam-moment traffic amortized — the r5 MFU lever)
    step = jax.jit(make_train_step(cfg, opt,
                                   accum_steps=config.get("accum", 1)))
    tokens = jax.device_put(
        jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0,
                           cfg.vocab_size),
        batch_sharding(mesh, FSDP_TP_RULES))
    with jax.set_mesh(mesh):
        for i in range(config["steps"]):
            params, opt_state, metrics = step(params, opt_state,
                                              {"tokens": tokens})
            session.report({"step": i, "loss": float(metrics["loss"])})


def main():
    import ray_tpu
    ray_tpu.init(num_cpus=4)
    result = JaxTrainer(
        train_loop, train_loop_config={"steps": 3, "accum": 2},
        scaling_config=ScalingConfig(num_workers=2),
    ).fit()
    if result.error is not None:
        raise result.error
    print("final loss:", result.metrics["loss"])
    assert result.metrics["loss"] < 10
    print("EXAMPLE_OK train_sharded_lm")
    ray_tpu.shutdown()


if __name__ == "__main__":
    main()
