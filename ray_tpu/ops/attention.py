"""Attention dispatch: Pallas flash kernel on TPU, reference jnp elsewhere.

All shapes are ``[batch, seq, heads, head_dim]`` with KV heads a divisor of
query heads (GQA).  `multi_head_attention` picks the implementation:

  * ``"flash"``  — `ray_tpu.ops.flash_attention` (TPU Pallas kernel);
    under a mesh set with `jax.set_mesh` the kernel runs per shard, batch
    over ``dp``/``fsdp`` and heads over ``tp``
  * ``"reference"`` — pure jnp (XLA-fused; used on CPU and for odd shapes)
  * ``"ring"``   — sequence-parallel ring attention
    (`ray_tpu.ops.ring_attention`, shards over the ``sp`` mesh axis)
  * ``"auto"``   — flash when on TPU and shapes are block-aligned
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .flash_attention import (DEFAULT_BLOCK_K, DEFAULT_BLOCK_Q, fit_block,
                              flash_attention, tile_ok)


def repeat_kv(x: jnp.ndarray, n_rep: int) -> jnp.ndarray:
    """Expand [b, s, h_kv, d] → [b, s, h_kv*n_rep, d] for GQA fallbacks."""
    if n_rep == 1:
        return x
    b, s, h, d = x.shape
    return jnp.broadcast_to(x[:, :, :, None, :],
                            (b, s, h, n_rep, d)).reshape(b, s, h * n_rep, d)


def reference_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                        causal: bool = True,
                        sm_scale: Optional[float] = None,
                        window: Optional[int] = None,
                        sink: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Plain softmax(QKᵀ)V with fp32 statistics; the correctness oracle for
    the flash kernel and the CPU execution path.  ``window`` (causal only):
    row i sees the keys j <= i with i - j < window.  ``sink`` [heads]: a
    learned logit a query head that joins every row's softmax denominator
    and takes no value (a row's probabilities then sum to less than 1).
    The values' head width may differ from the queries' and keys'."""
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    n_rep = q.shape[2] // k.shape[2]
    k, v = repeat_kv(k, n_rep), repeat_kv(v, n_rep)
    # [b, h, s_q, s_k]
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * sm_scale
    if causal:
        s_q, s_k = q.shape[1], k.shape[1]
        rows = jnp.arange(s_q)[:, None] + (s_k - s_q)
        mask = rows >= jnp.arange(s_k)[None, :]
        if window is not None:
            mask &= rows - jnp.arange(s_k)[None, :] < window
        # additive bias rather than jnp.where: a select against an invariant
        # constant inside a partial-manual shard_map scan (the pp pipeline)
        # trips an XLA partitioner CHECK ("invalid binary opcode copy");
        # adds fuse into the matmul epilogue anyway
        s = s + (1.0 - mask.astype(jnp.float32)) * -1e30
    p = sink_softmax(s, None if sink is None else sink[None, :, None, None])
    out = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)


def sink_softmax(scores: jnp.ndarray, sink: Optional[jnp.ndarray]
                 ) -> jnp.ndarray:
    """Float32 scores [..., rows] -> probabilities over the rows.  With
    ``sink`` (a learned logit a query head, shaped to broadcast against
    ``scores[..., :1]``) the denominator holds ``exp(sink)`` too: the sink
    takes no value, so a row then sums to less than 1."""
    if sink is None:
        return jax.nn.softmax(scores, axis=-1)
    sink = sink.astype(jnp.float32)
    m = jnp.maximum(scores.max(axis=-1, keepdims=True), sink)
    e = jnp.exp(scores - m)
    return e / (e.sum(axis=-1, keepdims=True) + jnp.exp(sink - m))


def _flash_ok(q: jnp.ndarray, k: jnp.ndarray,
              v: Optional[jnp.ndarray] = None,
              sink: Optional[jnp.ndarray] = None) -> bool:
    if jax.default_backend() != "tpu":
        return False
    s_q, s_kv, d = q.shape[1], k.shape[1], q.shape[-1]
    # the kernels take ONE head width (`flash_attention.make_plan`) and
    # have no sink: values of another width and a sink softmax are the
    # plain implementation's
    if sink is not None or (v is not None and v.shape[-1] != d):
        return False
    # heads of 64 share the 128 lanes in pairs and a multiple of 128 fills
    # them; the kernels lay out no other head size densely
    if d % 64:
        return False
    # the kernels' own rule for the tiles `flash_attention` would pick: a
    # multiple of 128 rows divides the sequence (the statistics travel with
    # the sequence on the lanes), or the whole (short) sequence is one tile
    return tile_ok(fit_block(DEFAULT_BLOCK_Q, s_q), s_q) and \
        tile_ok(fit_block(DEFAULT_BLOCK_K, s_kv), s_kv)


def _flash_per_shard(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                     causal: bool, sm_scale: Optional[float]) -> jnp.ndarray:
    """The flash kernel under the ambient mesh.  The compiler cannot
    partition a Mosaic kernel itself ("wrap the call in a shard_map"), so a
    sharded program runs it per shard: attention is independent across
    batch rows and heads, which is how the rules of `parallel/sharding.py`
    lay activations out (batch over ``dp`` x ``fsdp``, heads over ``tp``).
    An axis that does not divide its dimension stays unsharded here."""
    kernel = functools.partial(flash_attention, causal=causal,
                               sm_scale=sm_scale)
    mesh = jax.sharding.get_abstract_mesh()
    sizes = dict(mesh.shape) if mesh is not None else {}
    if math.prod(sizes.values() or (1,)) == 1:
        return kernel(q, k, v)
    batch = tuple(a for a in ("dp", "fsdp") if sizes.get(a, 1) > 1)
    if q.shape[0] % math.prod(sizes[a] for a in batch):
        batch = ()
    tp = sizes.get("tp", 1)
    heads = "tp" if tp > 1 and q.shape[2] % tp == 0 \
        and k.shape[2] % tp == 0 else None
    spec = P(batch or None, None, heads, None)
    # check_vma off: pallas_call declares no varying-axes rule
    return jax.shard_map(kernel, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)(q, k, v)


@jax.named_scope("attention")
def multi_head_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                         causal: bool = True,
                         sm_scale: Optional[float] = None,
                         impl: str = "auto",
                         window: Optional[int] = None,
                         sink: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """``sink`` [heads] (a logit a query head in the softmax's denominator)
    and values of another head width than the keys' exist in the plain
    implementation only, as the window mask does.
    ``window`` (a sliding-window layer, causal): a sequence no longer
    than the window is plain causal attention, whatever the implementation;
    a longer one takes the reference path, the one implementation with a
    window mask (the flash and ring kernels have none: a windowed model
    serves through the cached programs of `models/generate.py`)."""
    if window is not None and not causal:
        raise ValueError("a sliding window is a causal mask")
    if window is not None and k.shape[1] <= window:
        window = None
    plain_only = window is not None or sink is not None \
        or v.shape[-1] != q.shape[-1]
    if plain_only and impl not in ("auto", "reference"):
        raise NotImplementedError(
            f"attention impl {impl!r} has no window mask (a sequence of "
            f"{k.shape[1]} > window {window}), no sink and one head width "
            f"(values of {v.shape[-1]} beside keys of {q.shape[-1]}): "
            f"needs 'reference'")
    if impl == "auto":
        impl = "flash" if window is None and _flash_ok(q, k, v, sink) \
            else "reference"
    if impl == "flash":
        return _flash_per_shard(q, k, v, causal=causal, sm_scale=sm_scale)
    if impl == "reference":
        return reference_attention(q, k, v, causal=causal, sm_scale=sm_scale,
                                   window=window, sink=sink)
    if impl == "ring":
        # sequence-parallel path: shard_map over the ambient mesh's sp axis
        # (set the mesh with `jax.set_mesh` / `with mesh:` around the jit)
        from .ring_attention import ring_attention_shard
        mesh = jax.sharding.get_abstract_mesh()
        sp = dict(mesh.shape).get("sp", 1) if mesh is not None else 1
        if sp <= 1:
            return reference_attention(q, k, v, causal=causal,
                                       sm_scale=sm_scale)
        spec = P(None, "sp", None, None)
        return jax.shard_map(
            functools.partial(ring_attention_shard, axis_name="sp",
                              axis_size=sp, causal=causal,
                              sm_scale=sm_scale),
            in_specs=(spec, spec, spec), out_specs=spec)(q, k, v)
    raise ValueError(f"unknown attention impl {impl!r}")
