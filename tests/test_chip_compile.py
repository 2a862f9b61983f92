"""The chip's own compiler, asked before any chip call: the Pallas kernels of
the main path compiled for a described (not attached) ``v5e:2x2`` at the
shapes the smoke and the first cells use, and `create_mesh`'s TPU branch on
the described devices.

Nothing here runs on a device, and a compile that passes is not a chip run.
The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, so only the test worker
that is handed this file loads it, and it compiles in its own process.
"""

import math
import re

import pytest

# [batch, seq, heads, kv_heads, head_dim], bf16, the default tile:
# gpt2-medium train (chip_smoke.py), gpt2-small long context, llama GQA at
# two head widths, gpt2-medium at batch 8 (the one-chip train cell's micro
# batch), gpt2-xl's per-chip micro batch under fsdp=4 (25 heads: 3 x 8 + 1)
_SHAPES = [(4, 1024, 16, 16, 64), (2, 4096, 12, 12, 64),
           (2, 2048, 32, 8, 64), (2, 2048, 16, 4, 128),
           (8, 1024, 16, 16, 64), (2, 1024, 25, 25, 64)]


@pytest.fixture(scope="module")
def topo():
    import os
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-topology compile written to the persistent cache cannot
    # be read back without a chip: keep it off around these compiles
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


def _flash_plan(fa, shape):
    b, s, h, h_kv, d = shape
    return fa.make_plan(h, h_kv, d, s, s, 2,
                        fa.fit_block(fa.DEFAULT_BLOCK_Q, s),
                        fa.fit_block(fa.DEFAULT_BLOCK_K, s))


def _kernel_inputs(shape, plan, sharding):
    """Arguments of the kernels' own layout: the activations as they lie,
    ``[batch, seq, heads x head_dim]``, and the row statistics with the
    sequence on the lanes."""
    import jax
    import jax.numpy as jnp
    b, s, h, h_kv, d = shape

    def arr(*dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=sharding)

    q, kv = arr(b, s, h * d), arr(b, s, h_kv * d)
    row = arr(b, plan.head_blocks, plan.hq, s, dtype=jnp.float32)   # lse
    return q, kv, row


@pytest.mark.parametrize("kernel", ["fwd", "dq", "dkv"])
@pytest.mark.parametrize("shape", _SHAPES, ids=lambda s: "x".join(map(str, s)))
def test_flash_kernel_compiles_for_v5e(topo, shape, kernel):
    import importlib

    import jax
    from jax.sharding import SingleDeviceSharding

    # `ray_tpu.ops` re-exports the function under the module's own name
    fa = importlib.import_module("ray_tpu.ops.flash_attention")
    plan = _flash_plan(fa, shape)
    q, kv, row = _kernel_inputs(shape, plan,
                                SingleDeviceSharding(topo.devices[0]))
    scale = shape[-1] ** -0.5
    if kernel == "fwd":
        fn = lambda q, k, v: fa._flash_fwd(q, k, v, True, scale, plan)
        args = (q, kv, kv)
    else:
        # the backward is one kernel of three results (`one_backward`) or,
        # at a plan that does not hold the query side's accumulator, two:
        # reading dq alone, or dk and dv, leaves one call either way (the
        # one kernel whole; of the two, the one whose results are read)
        pick = slice(0, 1) if kernel == "dq" else slice(1, 3)
        fn = lambda q, k, v, o, lse, do: fa._flash_bwd(
            q, k, v, o, lse, do, True, scale, plan)[pick]
        args = (q, kv, kv, q, row, q)
    traced = jax.jit(fn).trace(*args)
    text = traced.lower().compile().as_text()
    (call,) = [line for line in text.splitlines() if "tpu_custom_call" in line]
    # every product of every kernel takes the operands' bfloat16 at the
    # default precision (the two-pass forward summed its exponentials with a
    # float32 `HIGHEST` product; the backward's five are the same five in
    # whatever order they are issued), and what the compiler scoped of VMEM
    # for the call stays under half the limit the kernels ask for, the bound
    # `one_backward` puts on `_block_bytes`' count (the hungriest: the one
    # backward kernel at 4 heads over 4096 rows, 20.25 MB)
    products = _dots(traced.jaxpr.jaxpr)
    assert len(products) >= (2 if kernel == "fwd" else 5)
    for eqn in products:
        assert eqn.params["precision"] in (None, (None, None)), eqn
        assert {v.aval.dtype.name for v in eqn.invars} == {"bfloat16"}
    used = re.search(r'"used_scoped_memory_configs":\[\{"memory_space":'
                     r'"1","offset":"0","size":"(\d+)"', call)
    assert 0 < int(used.group(1)) <= fa._VMEM_LIMIT // 2, used.group(0)


def _dots(jaxpr):
    """The `dot_general` equations of a jaxpr and of every jaxpr in it (a
    kernel's body, its loops and branches)."""
    import jax
    found = [e for e in jaxpr.eqns if e.primitive.name == "dot_general"]
    for eqn in jaxpr.eqns:
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _dots(sub)
    return found


@pytest.mark.parametrize("shape", [(8, 1024, 16, 16, 64),
                                   (2, 1024, 25, 25, 64)],
                         ids=lambda s: "x".join(map(str, s)))
def test_flash_calls_take_dense_operands_and_no_copy(topo, shape):
    """The train cells' attention, forward and backward, from activations
    as a projection leaves them (``[b, s, heads x 64]``): two calls, the
    forward and the one backward (dq beside dk and dv), and every operand
    and result of both has at least 128 lanes of data in its minor
    dimension (no ``[.., s, 1]`` statistics, no 64-wide heads), and the
    wrapper puts no transpose and no layout copy of an activation-sized
    array between them.  (At 25 heads the activations are 1600 wide, no
    multiple of 128: the compiler itself holds such an array sequence-minor
    wherever it may, parameters included, and converts it for any consumer
    that wants rows; only the operands' shapes are asserted there.)"""
    import re

    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from ray_tpu.ops.flash_attention import flash_attention
    b, s, h, h_kv, d = shape
    x = jax.ShapeDtypeStruct((b, s, h * d), jnp.bfloat16,
                             sharding=SingleDeviceSharding(topo.devices[0]))

    def loss(q, k, v):
        out = flash_attention(*(a.reshape(b, s, h, d) for a in (q, k, v)))
        return (out.reshape(b, s, h * d).astype(jnp.float32) ** 2).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        x, x, x).compile().as_text()
    calls = re.findall(r"= (\([^=]*\)|\S+) custom-call\(([^)]*)\), "
                       r"custom_call_target=\"tpu_custom_call\"", text)
    assert len(calls) == 2, calls
    # the forward's `o` beside float32 statistics; dq, dk, dv
    assert sorted(result.count("bf16[") for result, _ in calls) == [1, 3]
    big = b * s * h * d
    for result, operands in calls:
        shapes = re.findall(r"(?:bf16|f32)\[([\d,]+)\]", result)
        for name in re.findall(r"%([\w.\-]+)", operands):
            made = re.search(rf"%{re.escape(name)} = (\S+) (\w[\w\-]*)\(",
                             text)
            shapes += re.findall(r"\[([\d,]+)\]", made.group(1))
            dims = [int(n) for n in re.findall(
                r"\[([\d,]+)\]", made.group(1))[0].split(",")]
            assert (h * d) % 128 or not (
                made.group(2) in ("copy", "transpose")
                and math.prod(dims) >= big), made.group(0)
        assert shapes and all(int(sh.split(",")[-1]) >= 128
                              for sh in shapes), (result, operands, shapes)


@pytest.mark.parametrize("shape,parent_temp", [
    # the train cells' micro-batch a chip: gpt2-medium, gpt2-xl under fsdp=4;
    # `temp_size_in_bytes` of the same gradient under `nothing_saveable`
    # (what ``remat=True`` was before PR 44, compiled here, JAX 0.9.0)
    ((8, 1024, 16, 16, 64), 156866560), ((2, 1024, 25, 25, 64), 28762624),
], ids=["gpt2-medium-8x1024", "gpt2-xl-2x1024-a-chip"])
def test_full_remat_layer_gradient_holds_one_flash_forward(topo, shape,
                                                           parent_temp):
    """The gradient of one layer under the checkpoint ``remat=True`` gives
    it (`remat_policy`), as the chip's compiler leaves it: ONE forward
    kernel beside the one backward (the recompute pass wants no output of
    the call once its output and statistics are saved), and temporaries that
    grow by no more than those two arrays over what they were when the
    kernel ran twice."""
    import functools
    import importlib
    import re

    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from ray_tpu.models import TransformerConfig, init_params, transformer
    fa = importlib.import_module("ray_tpu.ops.flash_attention")
    b, s, h, _, d = shape
    cfg = TransformerConfig(vocab_size=128, d_model=h * d, n_layers=1,
                            n_heads=h, max_seq_len=s, attention_impl="flash")
    one = SingleDeviceSharding(topo.devices[0])
    stacked = jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), cfg)[0])["layers"]
    lp = jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape[1:], a.dtype, sharding=one), stacked)
    x = jax.ShapeDtypeStruct((b, s, h * d), jnp.bfloat16, sharding=one)
    layer = jax.checkpoint(functools.partial(transformer._layer, cfg),
                           policy=transformer.remat_policy(True))

    def loss(x, lp):
        return (layer(x, lp, {})[0].astype(jnp.float32) ** 2).sum()

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(x, lp).compile()
    calls = re.findall(r"custom_call_target=\"tpu_custom_call\".*",
                       compiled.as_text())
    assert sorted(re.search(r"flash_attention_\w+", c).group(0)
                  for c in calls) == ["flash_attention_bwd",
                                      "flash_attention_fwd"]
    plan = _flash_plan(fa, shape)
    kept = b * s * h * d * 2 + b * plan.head_blocks * plan.hq * s * 4
    assert compiled.memory_analysis().temp_size_in_bytes \
        <= parent_temp + kept


@pytest.mark.parametrize("shape", [
    # batch, s_q, s_kv, heads, kv heads, head size, causal
    (2, 192, 192, 12, 12, 64, True), (2, 197, 197, 12, 12, 64, False),
    (2, 100, 197, 4, 2, 64, True), (2, 200, 200, 8, 2, 128, True),
    (2, 192, 384, 12, 12, 64, True),
], ids=lambda s: "x".join(map(str, s)))
def test_flash_whole_sequence_tile_compiles_for_v5e(topo, shape):
    """A sequence the default tile does not divide (192; ViT's 197 tokens)
    is ONE tile of its own length: the chip's compiler takes the forward
    and the one backward at tiles that are no multiple of the lanes or the
    sublanes."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from ray_tpu.ops.attention import multi_head_attention
    b, s_q, s_kv, h, h_kv, d, causal = shape
    one = SingleDeviceSharding(topo.devices[0])

    def arr(s, heads):
        return jax.ShapeDtypeStruct((b, s, heads, d), jnp.bfloat16,
                                    sharding=one)

    def loss(q, k, v):
        out = multi_head_attention(q, k, v, causal=causal, impl="flash")
        return (out.astype(jnp.float32) ** 2).sum()

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        arr(s_q, h), arr(s_kv, h_kv), arr(s_kv, h_kv)).compile().as_text()
    assert text.count("tpu_custom_call") == 2


def test_flash_kernel_compiles_per_shard_on_the_2x2(topo):
    """The compiler refuses to partition a Mosaic kernel; under fsdp x tp
    `multi_head_attention` has to hand it one shard (ops/attention.py)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ray_tpu.ops.attention import multi_head_attention
    from ray_tpu.parallel import MeshSpec, create_mesh
    mesh = create_mesh(MeshSpec(fsdp=2, tp=2), devices=topo.devices)
    x = jax.ShapeDtypeStruct(
        (4, 1024, 16, 64), jnp.bfloat16,
        sharding=NamedSharding(mesh, P("fsdp", None, "tp", None)))

    def loss(q, k, v):
        out = multi_head_attention(q, k, v, impl="flash")
        return (out.astype(jnp.float32) ** 2).sum()

    with jax.set_mesh(mesh):
        text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
            x, x, x).compile().as_text()
    assert text.count("tpu_custom_call") >= 2          # fwd, bwd


def _computations(text):
    """HLO text -> {computation: its instruction lines}."""
    import re
    comps, cur = {}, None
    for line in text.splitlines():
        m = re.match(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .* \{$", line)
        if m:
            cur = comps.setdefault(m.group(1), [])
        elif line.startswith("}"):
            cur = None
        elif cur is not None:
            cur.append(line)
    return comps


_CALLS = r"(?:body|condition|to_apply|calls)=%?([\w.\-]+)"
_COLLECTIVE = (r"= (\S.*?) (all-reduce|all-gather|reduce-scatter|"
               r"collective-permute|all-to-all)(?:-start)?\((.*?)\)")


def _loop_bodies(text):
    """{while body: the lines of it and of what it calls, fusions included,
    an inner loop's body excluded (it is a body of its own)}."""
    import re
    comps = _computations(text)
    bodies = set(re.findall(r"body=%?([\w.\-]+)", text))

    def lines_of(name, seen):
        out = list(comps.get(name, ()))
        for line in comps.get(name, ()):
            for callee in re.findall(_CALLS, line):
                if callee not in seen and callee not in bodies:
                    seen.add(callee)
                    out += lines_of(callee, seen)
        return out

    return {b: lines_of(b, {b}) for b in bodies}


def _gpt2_step_text(topo, mesh_spec):
    """The train step of a small GPT-2 shape (layernorm with biases,
    learned positions, tied embedding, full remat, the flash kernel, 2
    micro-batches) as the chip's compiler leaves it: under ``mesh_spec`` on
    the described 2x2, or with None on one chip."""
    import contextlib

    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from jax.sharding import SingleDeviceSharding

    from ray_tpu.models import (TransformerConfig, init_params,
                                make_train_step)
    from ray_tpu.parallel import (FSDP_TP_RULES, MeshSpec, batch_sharding,
                                  create_mesh, pytree_shardings)
    cfg = TransformerConfig(
        vocab_size=512, d_model=256, n_layers=4, n_heads=4, max_seq_len=256,
        pos_emb="learned", activation="gelu", norm="layernorm",
        tie_embeddings=True, remat=True, attention_impl="flash")
    noted = {}

    def note():
        p, noted["axes"] = init_params(jax.random.PRNGKey(0), cfg)
        return p

    shapes = jax.eval_shape(note)
    axes = noted["axes"]
    if mesh_spec is None:
        mesh, one = None, SingleDeviceSharding(topo.devices[0])
        sh = jax.tree.map(lambda _: one, shapes)
        rep = batch = one
    else:
        mesh = create_mesh(MeshSpec.parse(mesh_spec), devices=topo.devices)
        sh = pytree_shardings(axes, mesh, FSDP_TP_RULES)
        rep, batch = NamedSharding(mesh, P()), \
            batch_sharding(mesh, FSDP_TP_RULES)
    params = jax.tree.map(lambda s, d: jax.ShapeDtypeStruct(
        s.shape, s.dtype, sharding=d), shapes, sh)
    opt = optax.adamw(3e-4, weight_decay=0.1)
    adam, *rest = jax.eval_shape(opt.init, params)
    # a moment lies as its parameter does (an eager `opt.init` on placed
    # parameters, as the benchmark's), the count on every chip
    opt_state = (type(adam)(
        count=jax.ShapeDtypeStruct((), jnp.int32, sharding=rep),
        mu=params, nu=params), *rest)
    tokens = jax.ShapeDtypeStruct((16, 256), jnp.int32, sharding=batch)
    step = jax.jit(make_train_step(cfg, opt, accum_steps=2),
                   donate_argnums=(0, 1))
    with jax.set_mesh(mesh) if mesh is not None \
            else contextlib.nullcontext():
        compiled = step.lower(params, opt_state, {"tokens": tokens}).compile()
    return compiled, cfg


def _vector_gathers(text, d):
    """The collectives inside loop bodies that GATHER a vector of ``d``
    numbers from its shards: an all-gather of one, or the partitioner's
    form for a small array, an all-reduce over shards each written into
    zeros (`dynamic-update-slice`).  (A gradient's sum over the chips is an
    all-reduce of a whole vector, and is not one of these.)"""
    import re
    found = []
    for body, lines in _loop_bodies(text).items():
        made = {m.group(1): m.group(2) for m in (
            re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = .*? ([\w\-]+)\(", ln)
            for ln in lines) if m}
        for ln in lines:
            m = re.search(_COLLECTIVE, ln)
            if not m:
                continue
            sizes = {math.prod(int(n) for n in dims.split(","))
                     for dims in re.findall(r"\[([\d,]+)\]", m.group(1))}
            if sizes != {d}:
                continue
            operands = re.findall(r"%([\w.\-]+)", m.group(3))
            if m.group(2) == "all-gather" or any(
                    made.get(o) == "dynamic-update-slice" for o in operands):
                found.append(ln.strip()[:160])
    return found


@pytest.mark.parametrize("cut_vectors", [False, True],
                         ids=["vectors-whole", "control-vectors-cut"])
def test_sharded_train_step_gathers_no_vector_in_its_loops(topo, monkeypatch,
                                                           cut_vectors):
    """Under fsdp=4 on the described 2x2 no loop body of the train step
    (micro-batches, layers forward, layers backward) gathers a norm's scale
    or bias: `pytree_shardings` leaves a vector whole on every chip.  The
    control cuts them as the rules did before PR 58 (like a matrix's rows)
    and finds the gathers (`all-reduce.92` / `.93` of gpt2-xl's step;
    PERF.md, PR 58)."""
    import jax

    from ray_tpu.parallel import sharding
    if cut_vectors:
        monkeypatch.setattr(sharding, "_whole_vectors", lambda ax: ax)
    compiled, cfg = _gpt2_step_text(topo, "fsdp=4")
    found = _vector_gathers(compiled.as_text(), cfg.d_model)
    assert bool(found) == cut_vectors, found
    if not cut_vectors:
        # the step hands back every parameter and moment as it was given
        # it: the benchmark calls the compiled step on its own results
        ins, outs = compiled.input_shardings[0], compiled.output_shardings
        for given, made in zip(ins[:2], outs[:2]):
            assert [s.is_fully_replicated for s in jax.tree.leaves(given)] \
                == [s.is_fully_replicated for s in jax.tree.leaves(made)]


def test_train_step_with_no_mesh_holds_no_layout_and_no_collective(topo):
    """With no mesh there is one layout: the step on one chip gets no
    sharding annotation from `_stepped` and no collective (the one-chip
    train cell's program is the one it was)."""
    compiled, _ = _gpt2_step_text(topo, None)
    text = compiled.as_text()
    assert "all-reduce" not in text and "all-gather" not in text
    assert 'custom_call_target="Sharding"' not in text
    assert text.count("tpu_custom_call") >= 2


def test_create_mesh_tpu_branch_on_the_described_2x2(topo):
    """`mesh_utils.create_device_mesh` with all six named axes, four of
    them trivial, on real (described) v5e coordinates."""
    from ray_tpu.parallel import MESH_AXES, MeshSpec, create_mesh
    assert topo.devices[0].platform == "tpu"
    mesh = create_mesh(MeshSpec(fsdp=2, tp=2), devices=topo.devices)
    assert mesh.axis_names == MESH_AXES
    assert dict(mesh.shape) == {"dp": 1, "fsdp": 2, "pp": 1, "sp": 1,
                                "tp": 2, "ep": 1}
    assert sorted(d.id for d in mesh.devices.flat) == sorted(
        d.id for d in topo.devices)
    small = create_mesh(MeshSpec(fsdp=2, tp=2), devices=topo.devices,
                        drop_trivial_axes=True)
    assert small.axis_names == ("fsdp", "tp") and small.devices.shape == (2, 2)


@pytest.mark.parametrize("model", ["gpt2-medium", "llama-1b", "llama-8b"])
@pytest.mark.parametrize("program", ["fused_step", "prefill_chunk_32",
                                     "prefill_chunk_1", "prefill_padded_32",
                                     "prefill_padded_128",
                                     "prefill_padded_256",
                                     "prefill_lanes_4x128"])
def test_served_program_converts_no_cache_on_the_v5e(topo, program, model):
    """At gpt2 head widths (16 heads of 64) the chip keeps a KV cache with
    ``max_len`` minor, whatever the logical order; a layer loop that
    wanted another order converted the whole cache before and after
    (`copy.*` led both served cells).  With the cache stored positions
    last and the loop held to that layout, the donated program aliases
    its cache, allocates a small fraction of one beside it, and no
    instruction copies an array of the cache's shape.  The same holds for
    RoPE/GQA widths: 8 kv heads of 64 and of 128.  Two layers of each
    model: the loop's body is compiled once whatever their number."""
    import dataclasses
    import re

    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from ray_tpu.models import (TransformerConfig, decode_step_slots,
                                init_kv_cache, init_params, init_slot_cache,
                                prefill_chunk)
    family, size = model.split("-")
    cfg = dataclasses.replace(
        getattr(TransformerConfig, family)(
            size, dtype=jnp.bfloat16, param_dtype=jnp.bfloat16,
            max_seq_len=1024), n_layers=2)
    one = SingleDeviceSharding(topo.devices[0])

    def described(tree):
        return jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one),
            tree)
    params = described(jax.eval_shape(
        lambda k: init_params(k, cfg)[0], jax.random.PRNGKey(0)))
    slots, max_len = 16, 1024
    if program == "fused_step":
        cache = described(jax.eval_shape(
            lambda: init_slot_cache(cfg, slots, max_len)))

        def fused_step(params, tok, cache, active):
            logits, cache = decode_step_slots(params, tok, cache, active,
                                              cfg)
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return jnp.where(active, nxt, tok), cache
        lowered = jax.jit(fused_step, donate_argnums=(2,)).lower(
            params, described(jax.ShapeDtypeStruct((slots,), jnp.int32)),
            cache, described(jax.ShapeDtypeStruct((slots,), jnp.bool_)))
    elif program.startswith("prefill_lanes"):
        cache, lowered, slots, width = _lower_lanes(described, params, cfg,
                                                    program, max_len)
    else:
        width = int(program.rsplit("_", 1)[1])
        cache = described(jax.eval_shape(
            lambda: init_kv_cache(cfg, 1, max_len)))
        # the engine's one shape: the count of real tokens a traced scalar
        padded = {"n_valid": described(jax.ShapeDtypeStruct((), jnp.int32))} \
            if program.startswith("prefill_padded") else {}
        lowered = jax.jit(prefill_chunk, static_argnames=("cfg",),
                          donate_argnames=("cache",)).lower(
            params, described(jax.ShapeDtypeStruct((1, width), jnp.int32)),
            cache, cfg=cfg, **padded)
    compiled = lowered.compile()
    ma = compiled.memory_analysis()
    want = 2 * cache["k"].size * cache["k"].dtype.itemsize
    assert ma.alias_size_in_bytes >= want
    # ... and, where lanes stack their rows, the stacked rows' FFN
    # activations (up and gate), which a batch-1 chunk's fit beside
    room = 2 * slots * width * cfg.ff_dim * 2 \
        if program.startswith("prefill_lanes") else 0
    assert ma.temp_size_in_bytes < want // 4 + room, (
        ma.temp_size_in_bytes, want)
    shape = ",".join(map(str, cache["k"].shape))
    copies = re.findall(rf"= bf16\[{shape}\]\S* copy\(", compiled.as_text())
    assert not copies, copies
    if program == "fused_step":
        _one_write_an_array(compiled.as_text(), cache)


def _one_write_an_array(text, cache):
    """A decode step's compiled text: every slot's new column goes through
    `ops/cache_write.py`'s kernel, called in place on each array of the
    cache, and of the ``slots`` one-column ``dynamic-update-slice``s an
    array a layer that the step held before it (32 at gpt2-medium's 16
    slots) at most one an array is left."""
    import re
    calls = [c for c in re.findall(r"= [^\n]* custom-call\([^\n]*", text)
             if "cache_column_write" in c]
    # the arrays that hold positions (a conv state is placed whole)
    shapes = [",".join(map(str, a.shape)) for name, a in cache.items()
              if name != "pos" and not name.endswith("_state")]
    for shape in set(shapes):
        mine = [c for c in calls if re.match(rf"= bf16\[{shape}\]", c)]
        assert mine and all(
            "output_to_operand_aliasing={{}: (3, {})}" in c for c in mine), (
            shape, calls)
        slices = re.findall(
            rf"= bf16\[{shape}\]\S* dynamic-update-slice\(", text)
        assert len(slices) <= shapes.count(shape), (shape, len(slices))


def test_byte_model_step_writes_one_call_an_array_on_the_v5e(topo):
    """The byte cell's step (its file's widths, two of its layers, 12 slots
    x 26624): rings of 2176 rows beside 1664 summary rows, 32 heads of 128.
    The donated step aliases all four arrays, keeps under a quarter of the
    cache beside it, copies none, and writes each through ONE kernel call a
    layer: the ring's column ``pos % ring`` and the summary's row ``pos //
    16`` of all 12 slots (48 slices a layer before)."""
    import re

    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from perfbench import manifest as mf
    from ray_tpu.models import init_params, init_slot_cache
    from ray_tpu.models.generate import _decode_step_slots, cache_arrays
    c = mf.Manifest().config("evabyte")
    cfg = mf.family_of(c).model.model_config(
        dict(c, num_hidden_layers=2), "serve")
    one = SingleDeviceSharding(topo.devices[0])

    def described(tree):
        return jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one),
            tree)
    params = described(jax.eval_shape(
        lambda k: init_params(k, cfg)[0], jax.random.PRNGKey(0)))
    slots, max_len = 12, 26624
    cache = described(jax.eval_shape(
        lambda: init_slot_cache(cfg, slots, max_len)))

    def fused_step(params, tok, cache, active):
        logits, cache, _ = _decode_step_slots(params, tok, cache, active,
                                              cfg)
        nxt = jnp.argmax(logits[..., :cfg.vocab_size], axis=-1)
        return jnp.where(active, nxt.astype(jnp.int32), tok), cache
    compiled = jax.jit(fused_step, donate_argnums=(2,)).lower(
        params, described(jax.ShapeDtypeStruct((slots,), jnp.int32)),
        cache, described(jax.ShapeDtypeStruct((slots,), jnp.bool_))
    ).compile()
    arrays = cache_arrays(cache)
    assert {n: a.shape for n, a in arrays.items()} == {
        "k_win": (2, 12, 32, 128, 2176), "v_win": (2, 12, 32, 128, 2176),
        "k_sum": (2, 12, 32, 128, 1664), "v_sum": (2, 12, 32, 128, 1664)}
    want = sum(a.size * a.dtype.itemsize for a in arrays.values())
    ma = compiled.memory_analysis()
    assert ma.alias_size_in_bytes >= want
    assert ma.temp_size_in_bytes < want // 4, (ma.temp_size_in_bytes, want)
    text = compiled.as_text()
    for a in arrays.values():
        shape = ",".join(map(str, a.shape))
        assert not re.findall(rf"= bf16\[{shape}\]\S* copy\(", text), shape
    _one_write_an_array(text, cache)


def test_byte_model_step_attends_the_blocks_a_slot_sees_on_the_v5e(topo):
    """The same step: its attention is ONE call of `ops/cache_attention.py`'s
    kernel a summary layer (one in the layer loop's body) over the four
    arrays where they lie, and no dot over a slot's 2176 ring rows or 1664
    summary rows is left (their float32 scores went with them); the chunk
    program and the lanes program, whose rows hold a chunk of queries, lower
    without THAT kernel (theirs is
    `test_byte_model_chunk_programs_attend_the_blocks_a_lane_sees_on_the_v5e`)."""
    import re

    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from perfbench import manifest as mf
    from ray_tpu.models import (init_kv_cache, init_params, init_slot_cache,
                                prefill_chunk)
    from ray_tpu.models.generate import _decode_step_slots
    c = mf.Manifest().config("evabyte")
    cfg = mf.family_of(c).model.model_config(
        dict(c, num_hidden_layers=2), "serve")
    one = SingleDeviceSharding(topo.devices[0])

    def described(tree):
        return jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one),
            tree)
    params = described(jax.eval_shape(
        lambda k: init_params(k, cfg)[0], jax.random.PRNGKey(0)))
    slots, max_len = 12, 26624
    cache = described(jax.eval_shape(
        lambda: init_slot_cache(cfg, slots, max_len)))

    def fused_step(params, tok, cache, active):
        logits, cache, _ = _decode_step_slots(params, tok, cache, active,
                                              cfg)
        return jnp.argmax(logits[..., :cfg.vocab_size], axis=-1), cache
    text = jax.jit(fused_step, donate_argnums=(2,)).lower(
        params, described(jax.ShapeDtypeStruct((slots,), jnp.int32)),
        cache, described(jax.ShapeDtypeStruct((slots,), jnp.bool_))
    ).compile().as_text()
    calls = [x for x in re.findall(r"= [^\n]* custom-call\([^\n]*", text)
             if "cache_block_attention" in x]
    assert len(calls) == 1, calls
    for shape in ("2,12,32,128,2176", "2,12,32,128,1664"):
        assert calls[0].count(f"bf16[{shape}]") == 2, calls[0]   # k and v
    scores = re.findall(r"f32\[[\d,]*,(?:2176|1664)\]", text)
    assert not scores, scores[:4]
    # a chunk of queries a row has a kernel of its own
    chunk = jax.jit(prefill_chunk, static_argnames=("cfg",)).lower(
        params, described(jax.ShapeDtypeStruct((1, 128), jnp.int32)),
        described(jax.eval_shape(lambda: init_kv_cache(cfg, 1, max_len))),
        cfg=cfg, n_valid=described(jax.ShapeDtypeStruct((), jnp.int32)))
    _, lanes, _, _ = _lower_lanes(described, params, cfg,
                                  "prefill_lanes_4x128", max_len)
    for lowered in (chunk, lanes):
        assert "cache_block_attention" not in lowered.as_text()


def _chunk_attention_calls(text):
    """The `ops/cache_attention.py` `attend_chunk_blocks` calls of a
    compiled program's text."""
    import re
    return [x for x in re.findall(r"= [^\n]* custom-call\([^\n]*", text)
            if "cache_chunk_attention" in x]


def test_byte_model_chunk_programs_attend_the_blocks_a_lane_sees_on_the_v5e(
        topo):
    """The byte cell's LANES program (4 x 128) and its lone chunk program
    (1 x 128): the attention is ONE call of `ops/cache_attention.py`'s chunk
    kernel a summary layer (one in the layer loop's body) over the four
    arrays where they lie; no lane's layer of a ring or of the summaries is
    cut out of the cache (`_lane_of`'s 63 MB a lane a layer), no float32
    score of 2176 or 1664 rows is left, and what the program keeps beside
    the cache is a few MB.  Lowered for a CPU the same programs hold no such
    call: the dense form, as the parent's."""
    import re

    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from perfbench import manifest as mf
    from ray_tpu.models import (init_kv_cache, init_params, init_slot_cache,
                                prefill_chunk, prefill_lanes)
    c = mf.Manifest().config("evabyte")
    cfg = mf.family_of(c).model.model_config(
        dict(c, num_hidden_layers=2), "serve")
    one = SingleDeviceSharding(topo.devices[0])

    def described(tree):
        return jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one),
            tree)
    shapes = jax.eval_shape(lambda k: init_params(k, cfg)[0],
                            jax.random.PRNGKey(0))
    params, max_len = described(shapes), 26624
    _, lanes, _, _ = _lower_lanes(described, params, cfg,
                                  "prefill_lanes_4x128", max_len)
    lone = jax.jit(prefill_chunk, static_argnames=("cfg",),
                   donate_argnames=("cache",)).lower(
        params, described(jax.ShapeDtypeStruct((1, 128), jnp.int32)),
        described(jax.eval_shape(lambda: init_kv_cache(cfg, 1, max_len))),
        cfg=cfg, n_valid=described(jax.ShapeDtypeStruct((), jnp.int32)))
    for rows, lowered in ((4, lanes), (1, lone)):
        compiled = lowered.compile()
        text = compiled.as_text()
        calls = _chunk_attention_calls(text)
        assert len(calls) == 1, calls
        for shape in (f"2,{rows},32,128,2176", f"2,{rows},32,128,1664"):
            assert calls[0].count(f"bf16[{shape}]") == 2, calls[0]  # k, v
        cuts = re.findall(
            r"= bf16\[(?:1,)*32,128,(?:2176|1664)\]\S* [a-z-]+\(", text)
        assert not cuts, cuts[:4]
        scores = re.findall(r"f32\[[\d,]*,(?:2176|1664)\]", text)
        assert not scores, scores[:4]
        assert compiled.memory_analysis().temp_size_in_bytes < 16 << 20
    # this process's own backend: the CPU's lowering is the dense form
    for program, tokens, cache in (
            (prefill_lanes, (4, 128), init_slot_cache(cfg, 4, 256)),
            (prefill_chunk, (1, 128), init_kv_cache(cfg, 1, 256))):
        cpu = jax.jit(program, static_argnames=("cfg",)).lower(
            shapes, jax.ShapeDtypeStruct(tokens, jnp.int32),
            jax.eval_shape(lambda: cache), cfg=cfg,
            n_valid=jax.ShapeDtypeStruct(tokens[:1] if program is
                                         prefill_lanes else (), jnp.int32))
        assert "cache_chunk_attention" not in cpu.as_text()


def test_delta_states_are_advanced_in_one_call_where_they_lie(topo):
    """A Kimi-shaped fused step (a dense KDA layer, a run of two, a latent
    layer; the published widths: 32 heads of 128 x 128 float32): every KDA
    layer's rule is ONE call of `ops/delta_rule.py`'s kernel (one in the
    dense layer's segment, one in the body of the KDA layers' loop) over the
    STACKED states, which go aliased from argument to result; nothing else
    in the program reads or writes an array of the states' shape or of a
    layer's (no cut, no placement, no copy, no convert, no fusion).  The
    chunk and lanes programs, whose rows hold a chunk of tokens, lower
    without the kernel: the chunkwise form between a cut and a placement,
    as the parent's."""
    import re

    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from perfbench import manifest as mf
    from ray_tpu.models import (init_kv_cache, init_params, init_slot_cache,
                                prefill_chunk)
    from ray_tpu.models.generate import _decode_step_slots
    c = mf.Manifest().config("kimi-linear-48b-a3b")
    c = dict(c, num_hidden_layers=4, linear_attn_config=dict(
        c["linear_attn_config"], kda_layers=[1, 2, 3], full_attn_layers=[4]))
    cfg = mf.family_of(c).model.model_config(c, "serve")
    one = SingleDeviceSharding(topo.devices[0])

    def described(tree):
        return jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one),
            tree)
    params = described(jax.eval_shape(
        lambda k: init_params(k, cfg)[0], jax.random.PRNGKey(0)))
    slots, max_len = 8, 1024
    cache = described(jax.eval_shape(
        lambda: init_slot_cache(cfg, slots, max_len)))
    assert cache["s_delta"].shape == (3, slots, 32, 128, 128)

    def fused_step(params, tok, cache, active):
        logits, cache, _ = _decode_step_slots(params, tok, cache, active,
                                              cfg)
        return jnp.argmax(logits[..., :cfg.vocab_size], axis=-1), cache
    compiled = jax.jit(fused_step, donate_argnums=(2,)).lower(
        params, described(jax.ShapeDtypeStruct((slots,), jnp.int32)),
        cache, described(jax.ShapeDtypeStruct((slots,), jnp.bool_))
    ).compile()
    arrays = [a for name, a in cache.items() if name != "pos"]
    assert compiled.memory_analysis().alias_size_in_bytes >= sum(
        a.size * a.dtype.itemsize for a in arrays)
    text = compiled.as_text()
    calls = [x for x in re.findall(r"= [^\n]* custom-call\([^\n]*", text)
             if "delta_rule_step" in x]
    assert len(calls) == 2, [x[:200] for x in calls]
    for call in calls:      # ``o`` and the stack out, the stack in place
        assert re.match(rf"= \(f32\[{slots},32,128\]\S* "
                        rf"f32\[3,{slots},32,128,128\]\S*\) custom-call\(",
                        call), call[:200]
        assert "output_to_operand_aliasing={{1}: (4, {})}" in call, call[:900]
    # who holds an array of the states' shape (or a layer's), and who uses it
    state = rf"f32\[(?:3,)?{slots},32,128,128\]"
    holders = set(re.findall(rf"(%[\w.\-]+) = [^\n]*?{state}", text))
    touched = {}
    for name, rest in re.findall(r"^\s*(?:ROOT )?(%[\w.\-]+) = ([^\n]*)$",
                                 text, re.M):
        op = re.search(r" ([a-z][a-z\-]*)\(([^\n]*)", " " + rest)
        if name in holders or holders & set(
                re.findall(r"%[\w.\-]+", op.group(2).split("), ")[0])):
            touched.setdefault(op.group(1), []).append(name)
    assert set(touched) <= {"parameter", "get-tuple-element", "tuple",
                            "while", "custom-call"}, {
        k: v[:3] for k, v in touched.items()}
    assert all("delta_rule_step" in n for n in touched["custom-call"])
    # a chunk of tokens a row: the chunkwise form, cut and placement
    chunk = jax.jit(prefill_chunk, static_argnames=("cfg",)).lower(
        params, described(jax.ShapeDtypeStruct((1, 128), jnp.int32)),
        described(jax.eval_shape(lambda: init_kv_cache(cfg, 1, max_len))),
        cfg=cfg, n_valid=described(jax.ShapeDtypeStruct((), jnp.int32)))
    _, lanes, _, _ = _lower_lanes(described, params, cfg,
                                  "prefill_lanes_4x128", max_len)
    for lowered in (chunk, lanes):
        text = lowered.as_text()
        assert "delta_rule_step" not in text
        assert "triangular_solve" in text


def test_state_space_states_are_advanced_in_one_call_where_they_lie(topo):
    """A Falcon-H1-shaped fused step (three layers of kind ``ssm+full`` at
    the published widths: 32 state heads of 128 with a state of 256 in 2
    groups, float32, beside 20 query heads over 4 key-value heads of 128):
    the loop's body holds ONE call of `ops/ssd.py`'s kernel over the STACKED
    states, which go aliased from argument to result with the keys and
    values of the SAME layers; nothing else reads or writes an array of the
    states' shape or of a layer's.  The chunk and lanes programs, whose rows
    hold a chunk of tokens, lower without the kernel: the chunkwise form
    between a cut and a placement."""
    import re

    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from perfbench import manifest as mf
    from ray_tpu.models import (init_kv_cache, init_params, init_slot_cache,
                                prefill_chunk)
    from ray_tpu.models.generate import _decode_step_slots
    c = dict(mf.Manifest().config("falcon-h1-34b"), num_hidden_layers=3)
    cfg = mf.family_of(c).model.model_config(c, "serve")
    one = SingleDeviceSharding(topo.devices[0])

    def described(tree):
        return jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one),
            tree)
    params = described(jax.eval_shape(
        lambda k: init_params(k, cfg)[0], jax.random.PRNGKey(0)))
    slots, max_len = 8, 1024
    cache = described(jax.eval_shape(
        lambda: init_slot_cache(cfg, slots, max_len)))
    assert cache["s_ssm"].shape == (3, slots, 32, 256, 128)
    assert cache["k"].shape == (3, slots, 4, 128, max_len)

    def fused_step(params, tok, cache, active):
        logits, cache, _ = _decode_step_slots(params, tok, cache, active,
                                              cfg)
        return jnp.argmax(logits, axis=-1), cache
    compiled = jax.jit(fused_step, donate_argnums=(2,)).lower(
        params, described(jax.ShapeDtypeStruct((slots,), jnp.int32)),
        cache, described(jax.ShapeDtypeStruct((slots,), jnp.bool_))
    ).compile()
    arrays = [a for name, a in cache.items() if name != "pos"]
    assert compiled.memory_analysis().alias_size_in_bytes >= sum(
        a.size * a.dtype.itemsize for a in arrays)
    text = compiled.as_text()
    calls = [x for x in re.findall(r"= [^\n]* custom-call\([^\n]*", text)
             if "ssd_step" in x]
    assert len(calls) == 1, [x[:200] for x in calls]
    assert re.match(rf"= \(f32\[{slots},32,128\]\S* "
                    rf"f32\[3,{slots},32,256,128\]\S*\) custom-call\(",
                    calls[0]), calls[0][:200]
    assert "output_to_operand_aliasing={{1}: (4, {})}" in calls[0]
    state = rf"f32\[(?:3,)?{slots},32,256,128\]"
    holders = set(re.findall(rf"(%[\w.\-]+) = [^\n]*?{state}", text))
    touched = {}
    for name, rest in re.findall(r"^\s*(?:ROOT )?(%[\w.\-]+) = ([^\n]*)$",
                                 text, re.M):
        op = re.search(r" ([a-z][a-z\-]*)\(([^\n]*)", " " + rest)
        if name in holders or holders & set(
                re.findall(r"%[\w.\-]+", op.group(2).split("), ")[0])):
            touched.setdefault(op.group(1), []).append(name)
    assert set(touched) <= {"parameter", "get-tuple-element", "tuple",
                            "while", "custom-call"}, {
        k: v[:3] for k, v in touched.items()}
    assert all("ssd_step" in n for n in touched["custom-call"])
    chunk = jax.jit(prefill_chunk, static_argnames=("cfg",)).lower(
        params, described(jax.ShapeDtypeStruct((1, 128), jnp.int32)),
        described(jax.eval_shape(lambda: init_kv_cache(cfg, 1, max_len))),
        cfg=cfg, n_valid=described(jax.ShapeDtypeStruct((), jnp.int32)))
    _, lanes, _, _ = _lower_lanes(described, params, cfg,
                                  "prefill_lanes_4x128", max_len)
    for lowered in (chunk, lanes):
        assert "ssd_step" not in lowered.as_text()


def test_thinking_cells_latent_layers_read_the_blocks_they_see(topo):
    """The thinking cell's programs whole (Kimi-Linear at the published
    widths, 27 layers, 32 slots x 5632 rows): the fused step holds SEVEN
    calls of `ops/latent_attention.py` `attend_cache`, one a latent layer
    (each is a loop segment of its own), over the stacked latents where they
    lie, with ONE int8 mask row a slot, and no array of scores over a slot's
    5632 rows in either type; the lanes program of 4 x 128 holds seven too
    and neither a lane's latent layer cut out of the cache nor a score
    block."""
    import re

    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from perfbench import manifest as mf
    from ray_tpu.models import init_params, init_slot_cache
    from ray_tpu.models.generate import _decode_step_slots
    c = mf.Manifest().config("kimi-linear-48b-a3b")
    cfg = mf.family_of(c).model.model_config(c, "serve")
    assert cfg.kinds.count("full") == 7
    one = SingleDeviceSharding(topo.devices[0])

    def described(tree):
        return jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one),
            tree)
    params = described(jax.eval_shape(
        lambda k: init_params(k, cfg)[0], jax.random.PRNGKey(0)))
    slots, max_len = 32, 5632
    cache = described(jax.eval_shape(
        lambda: init_slot_cache(cfg, slots, max_len)))
    assert cache["kv"].shape == (7, slots, 1, 576, max_len)

    def fused_step(params, tok, cache, active):
        logits, cache, _ = _decode_step_slots(params, tok, cache, active,
                                              cfg)
        return jnp.argmax(logits[..., :cfg.vocab_size], axis=-1), cache
    step = jax.jit(fused_step, donate_argnums=(2,)).lower(
        params, described(jax.ShapeDtypeStruct((slots,), jnp.int32)),
        cache, described(jax.ShapeDtypeStruct((slots,), jnp.bool_))
    ).compile()
    _, lanes, n_lanes, _ = _lower_lanes(described, params, cfg,
                                        "prefill_lanes_4x128", max_len)
    for compiled, rows, mask_rows in ((step, slots, 1),
                                      (lanes.compile(), n_lanes, 128)):
        text = compiled.as_text()
        calls = [x for x in re.findall(r"= [^\n]* custom-call\([^\n]*", text)
                 if "latent_attention_cache" in x]
        assert len(calls) == 7, len(calls)
        for call in calls:
            assert f"bf16[7,{rows},1,576,{max_len}]" in call, call[:300]
            assert f"s8[{rows},{mask_rows},{max_len}]" in call, call[:300]
        # no scores over all of a row's positions, no lane's layer cut out
        assert not re.findall(rf"= (?:f32|bf16)\[[\d,]*,{max_len}\]\S* "
                              rf"(?:fusion|convolution|dot)\(", text)
        assert not re.findall(rf"= bf16\[(?:1,)*576,{max_len}\]", text)
        assert compiled.memory_analysis().temp_size_in_bytes < 256 << 20


def test_notes_cells_two_latent_shapes_read_the_blocks_they_see(topo):
    """`dots3-note-prev` at the notes cell's real shapes, for the described
    v5e (no chip time): the fused slot step over 16 slots x 17,408 and the
    four-lane chunk program read BOTH latent arrays through
    `latent_attention_cache` where they lie, one call a loop segment: the
    three full layers' ``kv`` of 576-value rows (one layer in the dense run,
    two segments of the expert run) and the six sliding layers' RING of 768
    rows of 1088 values (two segments of three), the mask a row a slot (a
    step) or a row a query (a chunk); no lane's layer and no ring is cut out
    of its array, and the programs' scratch stays far under what is left
    beside 10.5 GB of arguments."""
    import re

    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from perfbench import manifest as mf
    from ray_tpu.models import init_slot_cache
    from ray_tpu.models.generate import _decode_step_slots
    c = mf.Manifest().config("dots3-note-prev")
    model = mf.family_of(c).model
    cfg = model.model_config(c, "serve")
    assert (cfg.kinds.count("index"), cfg.kinds.count("window")) == (3, 6)
    one = SingleDeviceSharding(topo.devices[0])

    def described(tree):
        return jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one),
            tree)
    params = described(jax.eval_shape(
        lambda k: model.make(k, c, jnp.bfloat16), jax.random.PRNGKey(0)))
    slots, max_len, ring = 16, 17408, 768
    cache = described(jax.eval_shape(
        lambda: init_slot_cache(cfg, slots, max_len)))
    assert {n: a.shape for n, a in cache.items() if n != "pos"} == {
        "kv": (3, slots, 1, 576, max_len), "kv_win": (6, slots, 1, 1088, ring),
        "k_idx": (3, slots, 1, 128, max_len)}

    def fused_step(params, tok, cache, active):
        logits, cache, _ = _decode_step_slots(params, tok, cache, active,
                                              cfg)
        return jnp.argmax(logits[..., :cfg.vocab_size], axis=-1), cache
    step = jax.jit(fused_step, donate_argnums=(2,)).lower(
        params, described(jax.ShapeDtypeStruct((slots,), jnp.int32)),
        cache, described(jax.ShapeDtypeStruct((slots,), jnp.bool_))
    ).compile()
    _, lanes, n_lanes, _ = _lower_lanes(described, params, cfg,
                                        "prefill_lanes_4x128", max_len)
    for compiled, rows, mask_rows in ((step, slots, 1),
                                      (lanes.compile(), n_lanes, 128)):
        text = compiled.as_text()
        calls = [x for x in re.findall(r"= [^\n]* custom-call\([^\n]*", text)
                 if "latent_attention_cache" in x]
        full = [x for x in calls if f"bf16[3,{rows},1,576,{max_len}]" in x]
        rings = [x for x in calls if f"bf16[6,{rows},1,1088,{ring}]" in x]
        assert (len(calls), len(full), len(rings)) == (5, 3, 2), len(calls)
        for call in full:
            assert f"s8[{rows},{mask_rows},{max_len}]" in call, call[:300]
        for call in rings:
            assert f"s8[{rows},{mask_rows},{ring}]" in call, call[:300]
        # no lane's layer and no ring cut out of its array (what stands
        # over all of a row's positions is the INDEXER's: one key of 128 a
        # position and its 64 heads' scores, XLA's dots)
        assert not re.findall(rf"= bf16\[(?:1,)*576,{max_len}\]", text)
        assert not re.findall(rf"= bf16\[(?:1,)*1088,{ring}\]", text)
        assert compiled.memory_analysis().temp_size_in_bytes < 512 << 20


def _lower_lanes(described, params, cfg, program, max_len):
    """``prefill_lanes_<P>x<C>``: the chunk program over P lanes of C rows
    (`models.generate.prefill_lanes`, what the engine runs while two or more
    prompts prefill) over a donated lane cache of P rows → (cache, lowered,
    P, C)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import init_slot_cache, prefill_lanes
    lanes, width = map(int, program.rsplit("_", 1)[1].split("x"))
    cache = described(jax.eval_shape(
        lambda: init_slot_cache(cfg, lanes, max_len)))
    lowered = jax.jit(prefill_lanes, static_argnames=("cfg",),
                      donate_argnames=("cache",)).lower(
        params, described(jax.ShapeDtypeStruct((lanes, width), jnp.int32)),
        cache, cfg=cfg,
        n_valid=described(jax.ShapeDtypeStruct((lanes,), jnp.int32)))
    return cache, lowered, lanes, width


def _grouped_calls(text):
    """The compiled program's calls of this repo's grouped matmul kernel;
    XLA's own grouped kernel must not be there beside them."""
    import re
    assert "ragged-dot" not in text
    return [c for c in re.findall(r"= [^\n]* custom-call\([^\n]*", text)
            if "grouped_matmul" in c]


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("shape", [
    (512, 5, 64, 2048, 1536), (512, 5, 64, 1536, 2048),    # GLM's chunk
    (64, 5, 64, 2048, 1536), (4, 5, 64, 1536, 2048),       # its step, a token
    (512, 4, 32, 3072, 3072), (64, 4, 32, 3072, 3072),     # Trinity's
    (1024, 4, 32, 3072, 3072),                             # a chunk of 256
], ids=lambda s: "x".join(map(str, s)))
def test_grouped_matmul_compiles_for_v5e(topo, shape, dtype):
    """The grouped matmul kernel at the served cells' call shapes: lowered
    for the described v5e it IS the kernel (the lowering platform chooses,
    `jax.lax.platform_dependent`), the stack is indexed where it lies (no
    temporary of a layer's experts), and its blocks fit the VMEM it asks
    for."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from ray_tpu.ops.grouped_matmul import grouped_matmul
    m, n_layers, n_groups, k, n = shape
    dt = jnp.dtype(dtype)
    one_chip = SingleDeviceSharding(topo.devices[0])

    def arg(sh, d):
        return jax.ShapeDtypeStruct(sh, d, sharding=one_chip)
    compiled = jax.jit(
        lambda a, st, l, g: grouped_matmul(a, (st, l), g)).lower(
        arg((m, k), dt), arg((n_layers, n_groups, k, n), dt),
        arg((), jnp.int32), arg((n_groups,), jnp.int32)).compile()
    assert len(_grouped_calls(compiled.as_text())) == 1
    assert compiled.memory_analysis().temp_size_in_bytes < (
        4 << 20) + 2 * max(m, 128) * n * dt.itemsize


@pytest.mark.parametrize("program", ["fused_step", "prefill_chunk_32",
                                     "prefill_chunk_1", "prefill_padded_32",
                                     "prefill_padded_128",
                                     "prefill_padded_256",
                                     "prefill_lanes_4x128"])
def test_latent_expert_model_copies_no_cache_and_no_expert_stack(topo,
                                                                 program):
    """Latent attention and routed experts at the published widths of the
    served cell's model (one dense and two expert layers): the donated
    program aliases its one cache array and copies none of its shape; the
    expert matmuls are this repo's grouped kernel (`ops/grouped_matmul.py`)
    also for ONE token (4 pairs); and no layer's slice of the expert stack
    is copied out for the kernel (it indexes the stack where it lies),
    which at first cost 18 of a chunk program's 25 ms (PERF.md, PR 28)."""
    import re

    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from ray_tpu.models import (TransformerConfig, init_kv_cache,
                                init_params, init_slot_cache, prefill_chunk)
    from ray_tpu.models.generate import _decode_step_slots
    cfg = TransformerConfig(
        vocab_size=154880, d_model=2048, n_layers=3, n_heads=20, d_ff=10240,
        max_seq_len=4096, pos_emb="rope", rope_base=1e6,
        activation="swiglu", norm="rmsnorm", norm_eps=1e-5,
        tie_embeddings=False, attention="mla", q_lora_rank=768,
        kv_lora_rank=512, qk_nope_head_dim=192, qk_rope_head_dim=64,
        v_head_dim=256, n_experts=64, expert_top_k=4, router="sigmoid",
        moe_d_ff=1536, n_shared_experts=1, routed_scaling_factor=1.8,
        first_dense_layers=1, dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    one = SingleDeviceSharding(topo.devices[0])

    def described(tree):
        return jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one),
            tree)
    params = described(jax.eval_shape(
        lambda k: init_params(k, cfg)[0], jax.random.PRNGKey(0)))
    slots, max_len = 16, 4096
    if program == "fused_step":
        cache = described(jax.eval_shape(
            lambda: init_slot_cache(cfg, slots, max_len)))

        def fused_step(params, tok, cache, active):
            logits, cache, load = _decode_step_slots(params, tok[:slots],
                                                     cache, active, cfg)
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return jnp.concatenate([jnp.where(active, nxt, tok[:slots]),
                                    jnp.stack(load)]), cache
        lowered = jax.jit(fused_step, donate_argnums=(2,)).lower(
            params, described(jax.ShapeDtypeStruct((slots + 2,), jnp.int32)),
            cache, described(jax.ShapeDtypeStruct((slots,), jnp.bool_)))
    elif program.startswith("prefill_lanes"):
        cache, lowered, slots, width = _lower_lanes(described, params, cfg,
                                                    program, max_len)
    else:
        width = int(program.rsplit("_", 1)[1])
        cache = described(jax.eval_shape(
            lambda: init_kv_cache(cfg, 1, max_len)))
        # the engine's one shape: the count of real tokens a traced scalar
        padded = {"n_valid": described(jax.ShapeDtypeStruct((), jnp.int32))} \
            if program.startswith("prefill_padded") else {}
        lowered = jax.jit(prefill_chunk, static_argnames=("cfg",),
                          donate_argnames=("cache",)).lower(
            params, described(jax.ShapeDtypeStruct((1, width), jnp.int32)),
            cache, cfg=cfg, **padded)
    compiled = lowered.compile()
    ma = compiled.memory_analysis()
    assert set(cache) == {"kv", "pos"}
    want = cache["kv"].size * cache["kv"].dtype.itemsize
    assert ma.alias_size_in_bytes >= want
    # beside the cache only activations: no second cache, no expert stack
    # (one layer's is 604 MB).  A chunk's float32 scores are `[width, 20,
    # 4096]`, 42 MB at the 128 rows the engine derives for a v5e and 84 MB
    # at 256; the compiler keeps two such arrays (170 MB in all at 256)
    rows = slots if program == "fused_step" else width
    scores = rows * cfg.n_heads * max_len * 4
    assert ma.temp_size_in_bytes < (64 << 20) + 2 * scores, \
        ma.temp_size_in_bytes
    text = compiled.as_text()
    if program == "fused_step":
        _one_write_an_array(text, cache)
    shape = ",".join(map(str, cache["kv"].shape))
    assert not re.findall(rf"= bf16\[{shape}\]\S* copy\(", text)
    # three grouped matmuls a layer, this repo's kernel and not XLA's, each
    # given the whole stack of 2 x 64 where it lies
    calls = _grouped_calls(text)
    assert len(calls) == 3 and all(
        "bf16[2,64,2048,1536]" in c or "bf16[2,64,1536,2048]" in c
        for c in calls), calls
    assert not re.findall(r"= bf16\[(?:\d+,)?64,(?:2048,1536|1536,2048)\]\S*"
                          r" copy\(", text)


@pytest.mark.parametrize("program", ["prefill_lanes_4x128",
                                     "prefill_padded_128"])
@pytest.mark.parametrize("heads,max_len,blocked", [(64, 6144, True),
                                                   (20, 4096, False)])
def test_blocked_latent_chunks_attend_in_one_kernel_call(topo, program,
                                                         heads, max_len,
                                                         blocked):
    """A latent model with an indexer (latents of 576, an indexing layer and
    two that share its choice: two layer bodies) at a chunk of 128 rows.
    Where the heads fill whole head tiles (64 heads over 6144 rows:
    `generate._key_block`, `mla.kernel_shape`) the lanes program and the
    batch-1 chunk program hold `ops/latent_attention.py` `attend_cache`'s
    call ONCE a layer body, no float32 array as large as a score block (128
    x 64 x 1024) and no cut of a lane's layer out of the cache; at
    glm-4.7-flash's 20 heads (no whole tile of 8) they hold no such call:
    42 MB of scores, read at once."""
    import re

    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from ray_tpu.models import (TransformerConfig, init_kv_cache,
                                init_params, prefill_chunk)
    cfg = TransformerConfig(
        vocab_size=1024, d_model=512, n_layers=3, n_heads=heads, d_ff=1024,
        max_seq_len=max_len, pos_emb="rope", rope_base=1e6,
        activation="swiglu", norm="rmsnorm", norm_eps=1e-5,
        tie_embeddings=False, attention="mla", q_lora_rank=256,
        kv_lora_rank=512, qk_nope_head_dim=192, qk_rope_head_dim=64,
        v_head_dim=256, index_heads=4, index_head_dim=128, index_topk=2048,
        layer_kinds=("index", "shared", "shared"), dtype=jnp.bfloat16,
        param_dtype=jnp.bfloat16)
    one = SingleDeviceSharding(topo.devices[0])

    def described(tree):
        return jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one),
            tree)
    params = described(jax.eval_shape(
        lambda k: init_params(k, cfg)[0], jax.random.PRNGKey(0)))
    if program.startswith("prefill_lanes"):
        cache, lowered, _, width = _lower_lanes(described, params, cfg,
                                                program, max_len)
    else:
        width = int(program.rsplit("_", 1)[1])
        cache = described(jax.eval_shape(
            lambda: init_kv_cache(cfg, 1, max_len)))
        lowered = jax.jit(prefill_chunk, static_argnames=("cfg",),
                          donate_argnames=("cache",)).lower(
            params, described(jax.ShapeDtypeStruct((1, width), jnp.int32)),
            cache, cfg=cfg,
            n_valid=described(jax.ShapeDtypeStruct((), jnp.int32)))
    compiled = lowered.compile()
    text = compiled.as_text()
    calls = [c for c in re.findall(r"= [^\n]* custom-call\([^\n]*", text)
             if "latent_attention_cache" in c]
    if not blocked:
        assert not calls
        return
    kv = cache["kv"]
    assert len(calls) == 2 and all(
        "bf16[" + ",".join(map(str, kv.shape)) + "]" in c for c in calls), \
        calls
    assert compiled.memory_analysis().alias_size_in_bytes >= sum(
        a.size * a.dtype.itemsize for n, a in cache.items() if n != "pos")
    # no score block: no float32 array of a chunk's queries x heads over a
    # block of rows (XLA's loop: [1, 128, 64, 1024]) or over all of them
    for dims in re.findall(r"f32\[([\d,]+)\]", text):
        dims = sorted(d for d in map(int, dims.split(",")) if d > 1)
        assert dims not in (sorted((width, heads, 1024)),
                            sorted((width, heads, max_len))), dims
    # the cache is read where it lies: no array of one lane's layer
    assert not re.findall(rf"= bf16\[(?:1,)*576,{max_len}\]", text)


@pytest.mark.parametrize("program", ["fused_step", "prefill_padded_128",
                                     "prefill_chunk_128", "prefill_chunk_1",
                                     "prefill_lanes_4x128"])
def test_window_and_full_layers_copy_no_cache_no_ring_no_weights(topo,
                                                                 program):
    """Window layers' rings beside a full layer's rows, at the published
    widths of the mixed cell's model (one dense and three expert layers, the
    kind changing INSIDE the run of expert layers, 32 of 256 experts held):
    the donated program aliases every array of both state kinds and copies
    none of their shapes (the two-piece write over the ring's seam is a
    read-modify-write of a chunk's columns, no branch that would copy the
    ring); the layer loop over PART of a run indexes the stacked weights and
    copies no layer's slice of an expert stack; and beside the cache there
    is room for activations only."""
    import re

    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from ray_tpu.models import (TransformerConfig, init_kv_cache,
                                init_params, init_slot_cache, prefill_chunk)
    from ray_tpu.models.generate import _decode_step_slots, cache_arrays
    cfg = TransformerConfig(
        vocab_size=25024, d_model=3072, n_layers=4, n_heads=48, n_kv_heads=8,
        head_size=128, d_ff=12288, max_seq_len=262144, pos_emb="rope",
        rope_layers="window", activation="swiglu", norm="rmsnorm",
        norm_eps=1e-5, tie_embeddings=False, qk_norm=True, attn_gate=True,
        sandwich_norm=True, embed_scale=3072 ** 0.5,
        layer_kinds=("window", "window", "window", "full"),
        sliding_window=4096, window_chunk=128, n_experts=256,
        experts_held=32, expert_top_k=4, router="sigmoid", moe_d_ff=3072,
        n_shared_experts=1, routed_scaling_factor=2.448,
        first_dense_layers=1, dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    assert [s[1:3] for s in cfg.layer_segments] == [(0, 1), (0, 2), (2, 1)]
    one = SingleDeviceSharding(topo.devices[0])

    def described(tree):
        return jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one),
            tree)
    params = described(jax.eval_shape(
        lambda k: init_params(k, cfg)[0], jax.random.PRNGKey(0)))
    slots, max_len = 16, 16896
    if program == "fused_step":
        cache = described(jax.eval_shape(
            lambda: init_slot_cache(cfg, slots, max_len)))

        def fused_step(params, tok, cache, active):
            logits, cache, load = _decode_step_slots(params, tok[:slots],
                                                     cache, active, cfg)
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return jnp.concatenate([jnp.where(active, nxt, tok[:slots]),
                                    jnp.stack(load)]), cache
        lowered = jax.jit(fused_step, donate_argnums=(2,)).lower(
            params, described(jax.ShapeDtypeStruct((slots + 3,), jnp.int32)),
            cache, described(jax.ShapeDtypeStruct((slots,), jnp.bool_)))
    elif program.startswith("prefill_lanes"):
        cache, lowered, slots, width = _lower_lanes(described, params, cfg,
                                                    program, max_len)
    else:
        width = int(program.rsplit("_", 1)[1])
        cache = described(jax.eval_shape(
            lambda: init_kv_cache(cfg, 1, max_len)))
        padded = {"n_valid": described(jax.ShapeDtypeStruct((), jnp.int32))} \
            if program.startswith("prefill_padded") else {}
        lowered = jax.jit(prefill_chunk, static_argnames=("cfg",),
                          donate_argnames=("cache",)).lower(
            params, described(jax.ShapeDtypeStruct((1, width), jnp.int32)),
            cache, cfg=cfg, **padded)
    compiled = lowered.compile()
    ma = compiled.memory_analysis()
    arrays = cache_arrays(cache)
    assert {n: a.shape[-1] for n, a in arrays.items()} == {
        "k": 16896, "v": 16896, "k_win": 4224, "v_win": 4224}
    want = sum(a.size * a.dtype.itemsize for a in arrays.values())
    assert ma.alias_size_in_bytes >= want
    # float32 scores of the full layer's rows, twice, and activations
    rows = slots if program == "fused_step" else width
    scores = rows * cfg.n_heads * max_len * 4
    assert ma.temp_size_in_bytes < (64 << 20) + 2 * scores, \
        ma.temp_size_in_bytes
    text = compiled.as_text()
    if program == "fused_step":
        _one_write_an_array(text, cache)
    for a in arrays.values():
        shape = ",".join(map(str, a.shape))
        assert not re.findall(rf"= bf16\[{shape}\]\S* copy\(", text), shape
    # a chunk program's attention is one kernel call a run of layers (a
    # window layer, two more, the full layer) over the kind's arrays where
    # they lie: no lane's layer is cut out of a ring or of the full rows,
    # and no float32 score of either is left
    attends = _chunk_attention_calls(text)
    if rows == 1 or program == "fused_step":    # one query a row: dense
        assert not attends
    else:
        batch = arrays["k"].shape[1]
        assert len(attends) == 3, attends
        assert [a.count(f"bf16[3,{batch},8,128,4224]") for a in attends] \
            == [2, 2, 0], attends
        assert attends[2].count(f"bf16[1,{batch},8,128,16896]") == 2
        if batch > 1:       # (`_lane_of`'s cuts; one row's arrays ARE so)
            assert not re.findall(
                r"= bf16\[(?:1,)*8,128,(?:4224|16896)\]\S* [a-z-]+\(",
                text)
        assert not re.findall(r"f32\[[\d,]*,(?:4224|16896)\]", text)
        assert ma.temp_size_in_bytes < 64 << 20, ma.temp_size_in_bytes
    # three grouped matmuls a segment of expert layers, each given the whole
    # stack of 3 x 32 experts; no slice of it is copied out
    calls = _grouped_calls(text)
    assert len(calls) == 6 and all(
        "bf16[3,32,3072,3072]" in c for c in calls), calls
    assert not re.findall(r"= bf16\[(?:\d+,)?32,3072,3072\]\S* copy\(", text)


@pytest.mark.parametrize("program", ["fused_step", "prefill_padded_128",
                                     "prefill_chunk_1",
                                     "prefill_lanes_4x128"])
def test_conv_states_beside_rows_copy_no_cache_no_state_no_weights(topo,
                                                                   program):
    """Conv layers' states beside an attention layer's rows, at the
    published widths of the reasoning cell's model (one dense conv layer,
    then an attention layer and three conv layers in ONE run of expert
    layers, all 32 experts held): the donated program aliases every array
    of both state kinds and copies none of their shapes (a state is read,
    advanced by the row's valid tokens and written back in place); the
    layer loop over PART of a run indexes each operator's stack by that
    operator's own counter and copies no stack of either, nor a layer's
    slice of an expert stack."""
    import re

    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from ray_tpu.models import (TransformerConfig, init_kv_cache,
                                init_params, init_slot_cache, prefill_chunk)
    from ray_tpu.models.generate import _decode_step_slots, cache_arrays
    cfg = TransformerConfig(
        vocab_size=65536, d_model=2048, n_layers=5, n_heads=32, n_kv_heads=8,
        d_ff=7168, max_seq_len=128000, pos_emb="rope", rope_base=1e6,
        activation="swiglu", norm="rmsnorm", norm_eps=1e-5,
        tie_embeddings=True, qk_norm=True,
        layer_kinds=("conv", "full", "conv", "conv", "conv"), conv_kernel=3,
        n_experts=32, expert_top_k=4, router="sigmoid", moe_d_ff=1792,
        first_dense_layers=1, dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    assert [s[1:] for s in cfg.layer_segments] == [
        (0, 1, "conv"), (0, 1, "full"), (1, 3, "conv")]
    one = SingleDeviceSharding(topo.devices[0])

    def described(tree):
        return jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one),
            tree)
    params = described(jax.eval_shape(
        lambda k: init_params(k, cfg)[0], jax.random.PRNGKey(0)))
    assert params["layers"]["conv_in"].shape == (3, 2048, 6144)
    assert params["layers"]["wq"].shape == (1, 2048, 32, 64)
    slots, max_len = 32, 4096
    if program == "fused_step":
        cache = described(jax.eval_shape(
            lambda: init_slot_cache(cfg, slots, max_len)))

        def fused_step(params, tok, cache, active):
            logits, cache, load = _decode_step_slots(params, tok[:slots],
                                                     cache, active, cfg)
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return jnp.concatenate([jnp.where(active, nxt, tok[:slots]),
                                    jnp.stack(load)]), cache
        lowered = jax.jit(fused_step, donate_argnums=(2,)).lower(
            params, described(jax.ShapeDtypeStruct((slots + 3,), jnp.int32)),
            cache, described(jax.ShapeDtypeStruct((slots,), jnp.bool_)))
    elif program.startswith("prefill_lanes"):
        cache, lowered, slots, width = _lower_lanes(described, params, cfg,
                                                    program, max_len)
    else:
        width = int(program.rsplit("_", 1)[1])
        cache = described(jax.eval_shape(
            lambda: init_kv_cache(cfg, 1, max_len)))
        padded = {"n_valid": described(jax.ShapeDtypeStruct((), jnp.int32))} \
            if program.startswith("prefill_padded") else {}
        lowered = jax.jit(prefill_chunk, static_argnames=("cfg",),
                          donate_argnames=("cache",)).lower(
            params, described(jax.ShapeDtypeStruct((1, width), jnp.int32)),
            cache, cfg=cfg, **padded)
    compiled = lowered.compile()
    ma = compiled.memory_analysis()
    arrays = cache_arrays(cache)
    batch = 1 if program.startswith("prefill_") and "lanes" not in program \
        else slots
    assert {n: a.shape for n, a in arrays.items()} == {
        "k": (1, batch, 8, 64, 4096), "v": (1, batch, 8, 64, 4096),
        "conv_state": (4, batch, 1, 2, 2048)}
    want = sum(a.size * a.dtype.itemsize for a in arrays.values())
    assert ma.alias_size_in_bytes >= want
    assert ma.temp_size_in_bytes < 64 << 20, ma.temp_size_in_bytes
    text = compiled.as_text()
    if program == "fused_step":
        _one_write_an_array(text, cache)
    for a in arrays.values():
        shape = ",".join(map(str, a.shape))
        assert not re.findall(rf"= bf16\[{shape}\]\S* copy\(", text), shape
    # neither operator's stack, whole or a layer's slice, is copied
    for shape in (r"(?:\d+,)?2048,6144", r"(?:\d+,)?2048,2048",
                  r"(?:\d+,)?2048,32,64", r"(?:\d+,)?32,64,2048"):
        assert not re.findall(rf"= bf16\[{shape}\]\S* copy\(", text), shape
    # three grouped matmuls a segment of expert layers, each given the whole
    # stack of 4 x 32 experts; no slice of it is copied out
    calls = _grouped_calls(text)
    assert len(calls) == 6 and all(
        re.search(r"bf16\[4,32,(2048,1792|1792,2048)\]", c)
        for c in calls), calls
    assert not re.findall(
        r"= bf16\[(?:\d+,)?32,(?:2048,1792|1792,2048)\]\S* copy\(", text)


@pytest.mark.parametrize("program", ["fused_step", "prefill_padded_128",
                                     "prefill_lanes_4x128"])
def test_two_cache_shapes_by_layer_kind_copy_no_cache_no_weights(topo,
                                                                 program):
    """Window layers of 8 key-value heads (rings of 128 + 128 rows, a sink
    a query head) beside full layers of 4, keys of 192 and values of 128, at
    the long-reasoning cell's own configuration and shapes (its file, all 1
    + 6 layers, 32 slots x 9728): the donated program aliases all FOUR cache
    arrays (two row shapes, two widths) and copies none of their shapes; the
    layer loop over PART of a run indexes each kind's own key and value
    stacks and copies no stack, nor a layer's slice of an expert stack; and
    beside the cache there is room for activations only."""
    import re

    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from perfbench import manifest as mf
    from ray_tpu.models import (init_kv_cache, init_params, init_slot_cache,
                                prefill_chunk)
    from ray_tpu.models.generate import _decode_step_slots, cache_arrays
    c = mf.Manifest().config("mimo-v2-flash")
    cfg = mf.family_of(c).model.model_config(c, "serve")
    assert [s[1:] for s in cfg.layer_segments] == [
        (0, 1, "full"), (0, 5, "window"), (5, 1, "full")]
    one = SingleDeviceSharding(topo.devices[0])

    def described(tree):
        return jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one),
            tree)
    params = described(jax.eval_shape(
        lambda k: init_params(k, cfg)[0], jax.random.PRNGKey(0)))
    assert params["layers"]["wk_win"].shape == (5, 4096, 8, 192)
    assert params["layers"]["wv"].shape == (1, 4096, 4, 128)
    assert params["layers"]["sink"].shape == (5, 64)
    slots, max_len = 32, 9728
    if program == "fused_step":
        cache = described(jax.eval_shape(
            lambda: init_slot_cache(cfg, slots, max_len)))

        def fused_step(params, tok, cache, active):
            logits, cache, load = _decode_step_slots(params, tok[:slots],
                                                     cache, active, cfg)
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return jnp.concatenate([jnp.where(active, nxt, tok[:slots]),
                                    jnp.stack(load)]), cache
        lowered = jax.jit(fused_step, donate_argnums=(2,)).lower(
            params, described(jax.ShapeDtypeStruct((slots + 3,), jnp.int32)),
            cache, described(jax.ShapeDtypeStruct((slots,), jnp.bool_)))
    elif program.startswith("prefill_lanes"):
        cache, lowered, slots, width = _lower_lanes(described, params, cfg,
                                                    program, max_len)
    else:
        width = int(program.rsplit("_", 1)[1])
        cache = described(jax.eval_shape(
            lambda: init_kv_cache(cfg, 1, max_len)))
        padded = {"n_valid": described(jax.ShapeDtypeStruct((), jnp.int32))} \
            if program.startswith("prefill_padded") else {}
        lowered = jax.jit(prefill_chunk, static_argnames=("cfg",),
                          donate_argnames=("cache",)).lower(
            params, described(jax.ShapeDtypeStruct((1, width), jnp.int32)),
            cache, cfg=cfg, **padded)
    compiled = lowered.compile()
    ma = compiled.memory_analysis()
    arrays = cache_arrays(cache)
    batch = 1 if program.startswith("prefill_") and "lanes" not in program \
        else slots
    assert {n: a.shape for n, a in arrays.items()} == {
        "k": (2, batch, 4, 192, 9728), "v": (2, batch, 4, 128, 9728),
        "k_win": (5, batch, 8, 192, 256), "v_win": (5, batch, 8, 128, 256)}
    want = sum(a.size * a.dtype.itemsize for a in arrays.values())
    assert want == batch * (9728 * 5120 + 5 * 256 * 5120)
    assert ma.alias_size_in_bytes >= want
    # beside the cache only activations.  The float32 scores of a full
    # layer are `[rows, 64, 9728]`: 80 MB at a step's 32 rows, 319 MB at a
    # chunk's 128, and the compiler keeps two such arrays
    rows = slots if program == "fused_step" else width
    scores = rows * cfg.n_heads * max_len * 4
    assert ma.temp_size_in_bytes < (96 << 20) + 2 * scores, \
        ma.temp_size_in_bytes
    text = compiled.as_text()
    if program == "fused_step":
        _one_write_an_array(text, cache)
    for a in arrays.values():
        shape = ",".join(map(str, a.shape))
        assert not re.findall(rf"= bf16\[{shape}\]\S* copy\(", text), shape
    # no kind's key stack, nor the queries' or the output's, whole or a
    # layer's slice, is copied.  A layer's VALUE projection is: the
    # compiler lays `[4096, 4 | 8, 128]` out by tiles of its few heads and
    # wants another order for the dot, one 4-8 MB copy a layer (three
    # instructions, one of them in the window layers' loop: 0.1 ms of a
    # step; PERF.md section 7)
    for shape in (r"(?:\d+,)?4096,[48],192", r"(?:\d+,)?4096,64,192",
                  r"(?:\d+,)?64,128,4096"):
        assert not re.findall(rf"= bf16\[{shape}\]\S* copy\(", text), shape
    assert len(re.findall(r"= bf16\[(?:\d+,)?4096,[48],128\]\S* copy\(",
                          text)) <= 3
    # a chunk program's FULL layers (no sink) attend through the chunk
    # kernel, one call a run of them, over the full rows where they lie;
    # the window layers, whose softmax a sink joins, keep the dense form
    attends = _chunk_attention_calls(text)
    if rows == 1 or program == "fused_step":    # one query a row: dense
        assert not attends
    else:
        assert len(attends) == 2, attends
        for a in attends:
            assert f"bf16[2,{batch},4,192,9728]" in a \
                and f"bf16[2,{batch},4,128,9728]" in a, a
            assert ",256]" not in a, a
        assert not re.findall(r"f32\[[\d,]*,9728\]", text)
        assert re.findall(r"f32\[[\d,]*,256\]", text)     # the rings'
    # three grouped matmuls a segment of expert layers, each given the whole
    # stack of 6 x 16 experts; no slice of it is copied out
    calls = _grouped_calls(text)
    assert len(calls) == 6 and all(
        re.search(r"bf16\[6,16,(4096,2048|2048,4096)\]", c)
        for c in calls), calls
    assert not re.findall(
        r"= bf16\[(?:\d+,)?16,(?:4096,2048|2048,4096)\]\S* copy\(", text)


@pytest.mark.parametrize("program", ["fused_step", "prefill_padded_128",
                                     "prefill_lanes_4x128"])
def test_layers_that_read_another_layers_cache_copy_nothing_on_the_v5e(
        topo, program):
    """A Phi-4-mini-flash-shaped model (six layers at the published widths:
    ``mamba, window, mamba, full, gmu, cross``; 40 query heads of 64 in pairs
    over 10 key rows of 128; a selective scan of 5120 channels with a state
    of 16) compiled for the described v5e: every cache array goes aliased
    from argument to result; NO copy of the stacked float32 states
    (``s_mamba``), of the one layer of rows or of a ring; the fused step
    attends the shared rows by ONE kernel call a READING layer that fetches
    a slot's visible blocks (`cache_block_attention`: the full layer and the
    cross layer, each rows and values from one pass); the chunk programs
    scan by the selective-scan kernel, a call a mamba layer, attend their
    chunk's blocks by the chunk kernel on the layers that run the chunk's
    rows, and run the stateless tail's cross layer on ONE row a lane through
    the step's kernel."""
    import re

    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from perfbench import manifest as mf
    from ray_tpu.models import (init_kv_cache, init_params, init_slot_cache,
                                prefill_chunk)
    from ray_tpu.models.generate import _decode_step_slots
    c = mf.Manifest().config("phi-4-mini-flash")
    kinds = ["mamba", "window", "mamba", "full", "gmu", "cross"]
    c = dict(c, num_hidden_layers=len(kinds),
             assumed=dict(c["assumed"], layer_kinds=kinds))
    cfg = mf.family_of(c).model.model_config(c, "serve")
    assert cfg.stateless_tail == 2
    one = SingleDeviceSharding(topo.devices[0])

    def described(tree):
        return jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one),
            tree)
    params = described(jax.eval_shape(
        lambda k: init_params(k, cfg)[0], jax.random.PRNGKey(0)))
    slots, max_len = 8, 2048
    if program == "fused_step":
        cache = described(jax.eval_shape(
            lambda: init_slot_cache(cfg, slots, max_len)))
        assert cache["k"].shape == (1, slots, 10, 128, max_len)
        assert cache["k_win"].shape == (1, slots, 10, 128, 640)
        assert cache["s_mamba"].shape == (2, slots, 1, 16, 5120)

        def fused_step(params, tok, cache, active):
            logits, cache, _ = _decode_step_slots(params, tok, cache,
                                                  active, cfg)
            return jnp.argmax(logits, axis=-1), cache
        lowered = jax.jit(fused_step, donate_argnums=(2,)).lower(
            params, described(jax.ShapeDtypeStruct((slots,), jnp.int32)),
            cache, described(jax.ShapeDtypeStruct((slots,), jnp.bool_)))
        rows = slots
    elif program == "prefill_padded_128":
        cache = described(jax.eval_shape(
            lambda: init_kv_cache(cfg, 1, max_len)))
        lowered = jax.jit(prefill_chunk, static_argnames=("cfg",),
                          donate_argnames=("cache",)).lower(
            params, described(jax.ShapeDtypeStruct((1, 128), jnp.int32)),
            cache, cfg=cfg,
            n_valid=described(jax.ShapeDtypeStruct((), jnp.int32)))
        rows = 1
    else:
        cache, lowered, rows, _ = _lower_lanes(described, params, cfg,
                                               program, max_len)
    compiled = lowered.compile()
    arrays = [a for name, a in cache.items() if name != "pos"]
    assert compiled.memory_analysis().alias_size_in_bytes >= sum(
        a.size * a.dtype.itemsize for a in arrays)
    text = compiled.as_text()
    copies = re.findall(r"= (\w+\[[\d,]*\])\S* copy\(", text)
    held = {f"{'f32' if a.dtype == jnp.float32 else 'bf16'}"
            f"[{','.join(map(str, shape))}]"
            for name, a in cache.items() if name not in ("pos",
                                                         "conv_mamba")
            for shape in (a.shape, a.shape[1:])}
    assert not held & set(copies), held & set(copies)
    calls = [x for x in re.findall(r"= [^\n]* custom-call\([^\n]*", text)
             if "tpu_custom_call" in x]

    def named(name):
        return sum(name in x for x in calls)
    if program == "fused_step":
        # the full layer and the cross layer, a call each; no chunk kernel
        assert named("cache_block_attention") == 2
        assert named("selective_scan_chunk") == 0
        assert named("cache_chunk_attention") == 0
    else:
        assert named("selective_scan_chunk") == 2       # a mamba layer each
        # the window and the full layer attend the chunk's blocks; the tail's
        # cross layer one row a lane
        assert named("cache_chunk_attention") == 2
        assert named("cache_block_attention") == 1
        # the tail's feed-forwards run on one row a lane: a dot of `rows`
        # rows against the feed-forward's width
        one_row = "10240" if rows == 1 else f"{rows},(?:1,)?10240"
        assert re.search(rf"\[{one_row}\]", text)
    if program == "prefill_padded_128":
        # the program of a chunk that is not its prompt's last: no tail, no
        # head (the embedding is read for its 128 rows alone)
        short = jax.jit(prefill_chunk, static_argnames=("cfg", "tail"),
                        donate_argnames=("cache",)).lower(
            params, described(jax.ShapeDtypeStruct((1, 128), jnp.int32)),
            cache, cfg=cfg, tail=False,
            n_valid=described(jax.ShapeDtypeStruct((), jnp.int32))
        ).compile().as_text()
        assert "200064]" in text and not re.search(
            r"= \w+\[(?:\d+,)*200064\]\S* (?:convolution|dot|fusion)\(", short)
        assert short.count("cache_block_attention") == 0
        assert short.count("selective_scan_chunk") >= 2


def test_pair_body_with_identity_experts_copies_no_cache_and_no_stack(topo):
    """The fused decode step of a SHORTCUT-CONNECTED model at the published
    widths of `longcat-flash-chat` (two published layers: four sublayers, two
    routers 768 wide over 512 experts, 16 held, and 256 identity experts):
    the donated program aliases its one cache of FOUR latent rows a position
    and copies none of its shape; a pair body holds three grouped matmuls,
    this repo's kernel, each given the whole stack of 2 x 16 experts where it
    lies (an identity pair joins no group: the stacks are all the kernel
    multiplies by), two blocked latent reads and two column writes; no
    sublayer's slice of a dense feed-forward or of the experts is copied out
    (the pair's sublayers are indices INTO the stacks); and FOUR routing sums
    ride behind the tokens."""
    import re

    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from ray_tpu.models import TransformerConfig, init_params, init_slot_cache
    from ray_tpu.models.generate import _decode_step_slots
    cfg = TransformerConfig(
        vocab_size=16384, d_model=6144, n_layers=4, n_heads=64, d_ff=12288,
        max_seq_len=131072, pos_emb="rope", rope_base=1e7,
        activation="swiglu", norm="rmsnorm", norm_eps=1e-5,
        tie_embeddings=False, attention="mla", q_lora_rank=1536,
        kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
        v_head_dim=128, latent_rescale=True, shortcut_moe=True,
        n_experts=512, experts_held=16, zero_experts=256, expert_top_k=12,
        router="softmax_bias", moe_d_ff=2048, routed_scaling_factor=6.0,
        dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    one = SingleDeviceSharding(topo.devices[0])

    def described(tree):
        return jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one),
            tree)
    params = described(jax.eval_shape(
        lambda k: init_params(k, cfg)[0], jax.random.PRNGKey(0)))
    slots, max_len = 64, 3584
    cache = described(jax.eval_shape(
        lambda: init_slot_cache(cfg, slots, max_len)))
    counts = []

    def fused_step(params, tok, cache, active):
        logits, cache, load = _decode_step_slots(params, tok[:slots], cache,
                                                 active, cfg)
        counts.append(len(load))
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return jnp.concatenate([jnp.where(active, nxt, tok[:slots]),
                                jnp.stack(load)]), cache
    compiled = jax.jit(fused_step, donate_argnums=(2,)).lower(
        params, described(jax.ShapeDtypeStruct((slots + 4,), jnp.int32)),
        cache, described(jax.ShapeDtypeStruct((slots,), jnp.bool_))).compile()
    assert counts == [4] == [cfg.load_counts]
    ma = compiled.memory_analysis()
    assert set(cache) == {"kv", "pos"} and cache["kv"].shape[0] == 4
    assert ma.alias_size_in_bytes >= cache["kv"].size * 2
    assert ma.temp_size_in_bytes < (256 << 20), ma.temp_size_in_bytes
    text = compiled.as_text()
    _one_write_an_array(text, cache)
    shape = ",".join(map(str, cache["kv"].shape))
    assert not re.findall(rf"= bf16\[{shape}\]\S* copy\(", text)
    calls = _grouped_calls(text)
    assert len(calls) == 3 and all(
        "bf16[2,16,6144,2048]" in c or "bf16[2,16,2048,6144]" in c
        for c in calls), calls
    # no sublayer's dense feed-forward (151 MB a matrix) and no layer's
    # experts copied out of their stacks
    assert not re.findall(r"= bf16\[(?:\d+,)?(?:6144,12288|12288,6144)\]\S*"
                          r" copy\(", text)
    assert not re.findall(r"= bf16\[(?:\d+,)?16,(?:6144,2048|2048,6144)\]\S*"
                          r" copy\(", text)
    assert "zero_experts" in text
