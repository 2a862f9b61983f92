"""parallel/ layer: mesh construction + logical sharding rules on the 8-device
virtual CPU mesh (the SURVEY §4 local-cluster test strategy applied to SPMD)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from ray_tpu.parallel import (
    FSDP_RULES, FSDP_TP_RULES, MeshSpec, ShardingRules, auto_mesh_shape,
    create_mesh, local_mesh, mesh_shape_for, named_sharding, shard_pytree,
)
from ray_tpu.parallel.mesh import pick_divisor_shape, slice_topology


def test_mesh_spec_resolve():
    assert MeshSpec(tp=4).resolve(8) == dict(
        dp=1, fsdp=2, pp=1, sp=1, tp=4, ep=1)
    assert MeshSpec(dp=2, fsdp=4).resolve(8)["fsdp"] == 4
    with pytest.raises(ValueError):
        MeshSpec(tp=3).resolve(8)
    with pytest.raises(ValueError):
        MeshSpec(dp=2, fsdp=2, tp=4).resolve(8)


def test_mesh_spec_parse():
    spec = MeshSpec.parse("dp=2, tp=4")
    assert spec.dp == 2 and spec.tp == 4 and spec.fsdp == -1
    with pytest.raises(ValueError):
        MeshSpec.parse("bogus=2")


def test_auto_mesh_shape():
    spec = auto_mesh_shape(8, model_parallel=2)
    assert spec.tp == 2 and spec.fsdp == 4
    assert mesh_shape_for(spec, 8) == (1, 4, 1, 1, 2, 1)


def test_create_mesh_axes():
    mesh = create_mesh(MeshSpec(fsdp=2, tp=4))
    assert mesh.axis_names == ("dp", "fsdp", "pp", "sp", "tp", "ep")
    assert mesh.devices.shape == (1, 2, 1, 1, 4, 1)
    small = create_mesh(MeshSpec(fsdp=2, tp=4), drop_trivial_axes=True)
    assert small.axis_names == ("fsdp", "tp")


def test_sharding_rules_spec():
    rules = ShardingRules(embed="fsdp", mlp="tp", batch=("dp", "fsdp"))
    assert rules.spec_for(("embed", "mlp")) == P("fsdp", "tp")
    assert rules.spec_for(None) == P()
    assert rules.with_overrides(mlp=None).spec_for(("mlp",)) == P(None)


def test_named_sharding_drops_missing_axes():
    mesh = local_mesh(fsdp=8)
    ns = named_sharding(mesh, ("embed", "mlp"), FSDP_TP_RULES)
    # tp axis exists (size 1) so nothing is dropped on the full canonical mesh
    assert ns.spec == P("fsdp", "tp")
    tiny = create_mesh(MeshSpec(fsdp=8), drop_trivial_axes=True)
    ns2 = named_sharding(tiny, ("embed", "mlp"), FSDP_TP_RULES)
    assert ns2.spec == P("fsdp", None)


def test_shard_pytree_places_params():
    mesh = local_mesh(fsdp=4, tp=2)
    params = {"w": jnp.zeros((8, 16)), "b": jnp.zeros((16,))}
    axes = {"w": ("embed", "mlp"), "b": ("mlp",)}
    sharded = shard_pytree(params, axes, mesh, FSDP_TP_RULES)
    w = sharded["w"]
    assert w.sharding.spec == P("fsdp", "tp")
    # each shard holds 8/4 x 16/2
    shard_shapes = {s.data.shape for s in w.addressable_shards}
    assert shard_shapes == {(2, 8)}


def test_fsdp_rules_matmul_psum():
    """End-to-end: a jit matmul under FSDP rules runs and matches numpy."""
    mesh = local_mesh(fsdp=8)
    x = np.random.RandomState(0).randn(16, 32).astype(np.float32)
    w = np.random.RandomState(1).randn(32, 8).astype(np.float32)
    xs = jax.device_put(x, named_sharding(mesh, ("batch", None), FSDP_RULES))
    ws = jax.device_put(w, named_sharding(mesh, ("embed", None), FSDP_RULES))
    out = jax.jit(lambda a, b: a @ b)(xs, ws)
    np.testing.assert_allclose(np.asarray(out), x @ w, rtol=1e-5)


def test_pick_divisor_shape_and_topology():
    assert pick_divisor_shape(8) == [2, 4]
    assert pick_divisor_shape(7) == [1, 7]
    info = slice_topology()
    assert info["device_count"] == 8


def test_kv_roundtrip(local_cluster):
    from ray_tpu.util import kv
    kv.kv_put("alpha", b"1", namespace="t")
    assert kv.kv_get("alpha", namespace="t") == b"1"
    assert kv.kv_exists("alpha", namespace="t")
    assert kv.kv_keys("al", namespace="t") == [b"alpha"]
    assert kv.kv_del("alpha", namespace="t")
    assert kv.kv_get("alpha", namespace="t") is None


def test_shape_aware_sharding_gqa_kv_replication():
    """tp wider than n_kv_heads: shape-aware pytree_shardings replicates
    the kv-head dim instead of erroring (the GQA-on-v4-32 class of bug
    the 16/32-device dryrun flushes out), while q keeps its tp shard."""
    from ray_tpu.parallel import pytree_shardings

    mesh = local_mesh(tp=4, sp=2, fsdp=1)
    params = {
        "wq": jnp.zeros((2, 64, 4, 16)),   # (layers, embed, heads=4, kv)
        "wk": jnp.zeros((2, 64, 2, 16)),   # kv_heads=2: 2 % tp4 != 0
    }
    axes = {"wq": ("layers", "embed", "heads", "kv"),
            "wk": ("layers", "embed", "heads", "kv")}
    sh = pytree_shardings(axes, mesh, FSDP_TP_RULES, params=params)
    assert sh["wq"].spec == P(None, "fsdp", "tp", None)
    assert sh["wk"].spec == P(None, "fsdp", None, None)
    # and the placement actually succeeds
    placed = jax.device_put(params, sh)
    assert placed["wk"].sharding.spec == P(None, "fsdp", None, None)


def test_shape_aware_sharding_without_params_unchanged():
    """No params given: pytree_shardings keeps the raw rule mapping (the
    pre-existing contract for shape-agnostic callers)."""
    from ray_tpu.parallel import pytree_shardings

    mesh = local_mesh(tp=4, sp=2, fsdp=1)
    sh = pytree_shardings({"wk": ("layers", "embed", "heads", "kv")},
                          mesh, FSDP_TP_RULES)
    assert sh["wk"].spec == P(None, "fsdp", "tp", None)


# step 1 of PR 58: a parameter that is a vector stays whole on every chip

_WHOLE = [("layers", "embed"), ("embed",), ("layers", "heads"),
          ("layers", None)]
_CUT = [(("embed", "mlp"), P("fsdp", "tp")),
        (("embed", None), P("fsdp", None)),
        (("heads", "kv", "embed"), P("tp", None, "fsdp")),
        (("vocab", "embed"), P("tp", "fsdp")),
        (("layers", "embed", "heads", "kv"), P(None, "fsdp", "tp", None)),
        ((None, "embed"), P(None, "fsdp"))]


def _shape_for(axes):
    return tuple({"layers": 2, "kv": 16}.get(a, 64) for a in axes)


@pytest.mark.parametrize("with_params", [False, True],
                         ids=["axes-alone", "with-params"])
@pytest.mark.parametrize("axes", _WHOLE, ids=lambda a: "-".join(map(str, a)))
def test_vector_leaf_is_replicated_under_fsdp(axes, with_params):
    """A norm's scale or bias (one dimension, "layers" apart) is not cut:
    4.8 KB a chip saved against a blocking collective at every use."""
    from ray_tpu.parallel import pytree_shardings

    mesh = local_mesh(fsdp=4, tp=2)
    params = {"v": jnp.zeros(_shape_for(axes))} if with_params else None
    sh = pytree_shardings({"v": axes}, mesh, FSDP_TP_RULES, params=params)
    assert sh["v"].is_fully_replicated, sh["v"].spec
    assert all(e is None for e in sh["v"].spec)


@pytest.mark.parametrize("with_params", [False, True],
                         ids=["axes-alone", "with-params"])
@pytest.mark.parametrize("axes,spec", _CUT,
                         ids=lambda a: "-".join(map(str, a))
                         if isinstance(a, tuple) else "")
def test_matrix_leaf_is_cut_as_the_rules_say(axes, spec, with_params):
    from ray_tpu.parallel import pytree_shardings

    mesh = local_mesh(fsdp=4, tp=2)
    params = {"w": jnp.zeros(_shape_for(axes))} if with_params else None
    sh = pytree_shardings({"w": axes}, mesh, FSDP_TP_RULES, params=params)
    assert sh["w"].spec == spec


def test_vector_leaf_keeps_the_stacking_axis_of_a_pipeline():
    """``layers="pp"`` cuts the stack of scales by stage; the scale itself
    stays whole."""
    from ray_tpu.parallel import pytree_shardings

    mesh = local_mesh(fsdp=2, pp=2, tp=2)
    rules = FSDP_TP_RULES.with_overrides(layers="pp")
    sh = pytree_shardings({"n": ("layers", "embed"),
                           "w": ("layers", "embed", "mlp")}, mesh, rules)
    assert sh["n"].spec == P("pp", None)
    assert sh["w"].spec == P("pp", "fsdp", "tp")


def test_gpt2_shape_places_vectors_whole_and_matrices_cut():
    """`init_params`' own axes under fsdp=4: every leaf of rank 1 (rank 2
    in a run's stack) is replicated, every other leaf is cut somewhere."""
    from ray_tpu.models import TransformerConfig, init_params
    from ray_tpu.parallel import pytree_shardings

    cfg = TransformerConfig.tiny(norm="layernorm", pos_emb="learned",
                                 activation="gelu", tie_embeddings=True,
                                 n_kv_heads=4)
    mesh = local_mesh(fsdp=4, tp=1, dp=2)
    params, axes = init_params(jax.random.PRNGKey(0), cfg)
    sh = pytree_shardings(axes, mesh, FSDP_TP_RULES, params=params)
    flat = jax.tree_util.tree_flatten_with_path(sh)[0]
    shapes = jax.tree_util.tree_leaves(params)
    whole = {jax.tree_util.keystr(path) for (path, s), p in zip(flat, shapes)
             if s.is_fully_replicated}
    assert whole == {"['final_norm']", "['final_norm_b']",
                     "['layers']['attn_norm']", "['layers']['attn_norm_b']",
                     "['layers']['mlp_norm']", "['layers']['mlp_norm_b']"}


def test_sharded_step_gives_the_unsharded_loss_and_gradients():
    """One GPT-2-shaped step (layernorm with biases, micro-batches, full
    remat) over the 8 host devices, vectors whole and matrices cut, against
    the same step on one device: loss, gradient norm and the parameters
    after the step, to `test_grad_accumulation_matches_full_batch`'s
    tolerances (`tests/test_train.py` holds none of its own)."""
    import optax

    from ray_tpu.models import (TransformerConfig, init_params, lm_loss,
                                make_train_step)
    from ray_tpu.parallel import batch_sharding, pytree_shardings

    cfg = TransformerConfig.tiny(norm="layernorm", pos_emb="learned",
                                 activation="gelu", tie_embeddings=True,
                                 n_kv_heads=4, remat=True, dtype=jnp.float32)
    params, axes = init_params(jax.random.PRNGKey(0), cfg)
    # zeros for biases and ones for scales have gradients all the same
    params = jax.tree.map(
        lambda p: p + 0.1 * jax.random.normal(jax.random.PRNGKey(3), p.shape),
        params)
    opt = optax.adamw(1e-3)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0,
                                cfg.vocab_size)
    step = make_train_step(cfg, opt, accum_steps=2)
    grad = lambda p, t: jax.grad(lm_loss)(p, {"tokens": t}, cfg)  # noqa: E731
    p1, _, m1 = jax.jit(step)(params, opt.init(params), {"tokens": tokens})
    g1 = jax.jit(grad)(params, tokens)

    mesh = local_mesh(dp=2, fsdp=4, tp=1)
    placed = jax.device_put(
        params, pytree_shardings(axes, mesh, FSDP_TP_RULES, params=params))
    assert placed["layers"]["attn_norm"].sharding.is_fully_replicated
    assert placed["layers"]["wq"].sharding.spec == P(None, "fsdp", "tp", None)
    toks = jax.device_put(tokens, batch_sharding(mesh, FSDP_TP_RULES))
    with jax.set_mesh(mesh):
        p2, _, m2 = jax.jit(step)(placed, opt.init(placed), {"tokens": toks})
        g2 = jax.jit(grad)(placed, toks)
    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]),
                               rtol=2e-4)
    np.testing.assert_allclose(float(m1["grad_norm"]),
                               float(m2["grad_norm"]), rtol=2e-3)
    # the step hands every leaf back as it was given it (a loop calls the
    # compiled step on its own results): the scales whole, the matrices cut
    for given, made in zip(jax.tree_util.tree_leaves(placed),
                           jax.tree_util.tree_leaves(p2)):
        assert made.sharding.is_equivalent_to(given.sharding, given.ndim)
    for a, b in zip(jax.tree_util.tree_leaves(g1),
                    jax.tree_util.tree_leaves(g2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(p1),
                    jax.tree_util.tree_leaves(p2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=4e-3)
