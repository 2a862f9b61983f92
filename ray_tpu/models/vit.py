"""ViT: vision transformer classification family.

The encoder-side model family complementing the decoder LMs in
`transformer.py` (an original addition — the reference framework ships
no model zoo; its vision path is the RLlib catalog's CNN).  TPU-first
like the LM trunk: patchify is a reshape + one matmul (MXU-friendly,
no gather), the encoder reuses the SAME `_layer` blocks (scan over
stacked weights, optional remat, flash/reference attention with
``causal=False``), and every parameter carries logical axes so
`parallel.pytree_shardings` shards it over dp/fsdp/tp meshes
unchanged.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .transformer import (TransformerConfig, _layer, _norm, init_params,
                          remat_policy)

Params = Any


@dataclasses.dataclass
class ViTConfig:
    image_size: int = 32
    patch_size: int = 4
    channels: int = 3
    num_classes: int = 10
    d_model: int = 192
    n_layers: int = 6
    n_heads: int = 4
    d_ff: Optional[int] = None
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    remat: bool = False
    attention_impl: str = "auto"

    @property
    def n_patches(self) -> int:
        side = self.image_size // self.patch_size
        return side * side

    @property
    def seq_len(self) -> int:
        return self.n_patches + 1          # +1 for the CLS token

    def block_cfg(self) -> TransformerConfig:
        """The encoder blocks are plain transformer layers with
        bidirectional attention — one shared implementation."""
        return TransformerConfig(
            vocab_size=8,                   # unused (embed is replaced)
            d_model=self.d_model, n_layers=self.n_layers,
            n_heads=self.n_heads, d_ff=self.d_ff,
            max_seq_len=self.seq_len, pos_emb="learned",
            activation="gelu", norm="layernorm", causal=False,
            dtype=self.dtype, param_dtype=self.param_dtype,
            remat=self.remat, attention_impl=self.attention_impl)

    @staticmethod
    def tiny(**kw) -> "ViTConfig":
        defaults = dict(image_size=16, patch_size=4, channels=1,
                        num_classes=4, d_model=64, n_layers=2,
                        n_heads=4)
        defaults.update(kw)
        return ViTConfig(**defaults)

    @staticmethod
    def base(**kw) -> "ViTConfig":
        """ViT-B/16 dimensions (public paper sizes)."""
        defaults = dict(image_size=224, patch_size=16, channels=3,
                        num_classes=1000, d_model=768, n_layers=12,
                        n_heads=12)
        defaults.update(kw)
        return ViTConfig(**defaults)


def init_vit_params(key: jax.Array, cfg: ViTConfig
                    ) -> Tuple[Params, Params]:
    """(params, logical axes).  Encoder layers come from the shared
    transformer initializer; embed/head are vision-specific."""
    kb, kp, kc, kpos, kh = jax.random.split(key, 5)
    base, base_axes = init_params(kb, cfg.block_cfg())
    pt = cfg.param_dtype
    d = cfg.d_model
    patch_dim = cfg.patch_size * cfg.patch_size * cfg.channels
    params: Params = {
        "layers": base["layers"],
        "final_norm": base["final_norm"],
        "final_norm_b": base["final_norm_b"],
        "patch": {
            "w": jax.random.normal(kp, (patch_dim, d), pt)
            / math.sqrt(patch_dim),
            "b": jnp.zeros((d,), pt),
        },
        "cls": jax.random.normal(kc, (1, 1, d), pt) * 0.02,
        "pos": jax.random.normal(kpos, (cfg.seq_len, d), pt) * 0.02,
        "head": {
            "w": jax.random.normal(kh, (d, cfg.num_classes), pt)
            / math.sqrt(d),
            "b": jnp.zeros((cfg.num_classes,), pt),
        },
    }
    axes: Params = {
        "layers": base_axes["layers"],
        "final_norm": base_axes["final_norm"],
        "final_norm_b": base_axes["final_norm_b"],
        "patch": {"w": (None, "embed"), "b": ("embed",)},
        "cls": (None, None, "embed"),
        "pos": (None, "embed"),
        "head": {"w": ("embed", "vocab"), "b": ("vocab",)},
    }
    return params, axes


def patchify(images: jnp.ndarray, cfg: ViTConfig) -> jnp.ndarray:
    """[b, H, W, C] → [b, n_patches, P*P*C] by pure reshape/transpose —
    no gather, no conv lowering surprises; the single following matmul
    is the whole embedding."""
    expect = (cfg.image_size, cfg.image_size, cfg.channels)
    if images.shape[1:] != expect:
        # a same-element-count layout mismatch (e.g. NCHW) would
        # reshape into scrambled patches and silently fail to learn
        raise ValueError(f"expected NHWC images [b, {expect[0]}, "
                         f"{expect[1]}, {expect[2]}], got "
                         f"{images.shape}")
    b = images.shape[0]
    p, side = cfg.patch_size, cfg.image_size // cfg.patch_size
    x = images.reshape(b, side, p, side, p, cfg.channels)
    x = x.transpose(0, 1, 3, 2, 4, 5)
    return x.reshape(b, side * side, p * p * cfg.channels)


def vit_forward(params: Params, images: jnp.ndarray,
                cfg: ViTConfig) -> jnp.ndarray:
    """[b, H, W, C] float images → [b, num_classes] logits."""
    bc = cfg.block_cfg()
    dt = cfg.dtype
    x = patchify(images.astype(dt), cfg)
    x = x @ params["patch"]["w"].astype(dt) + \
        params["patch"]["b"].astype(dt)
    cls = jnp.broadcast_to(params["cls"].astype(dt),
                           (x.shape[0], 1, cfg.d_model))
    x = jnp.concatenate([cls, x], axis=1) + params["pos"].astype(dt)

    layer = functools.partial(_layer, bc)
    policy = remat_policy(cfg.remat)
    if policy is not None:
        layer = jax.checkpoint(layer, policy=policy)

    def body(h, lp):
        h, _aux, _sel = layer(h, lp, {})    # learned positions: no rotary
        return h, None

    x, _ = jax.lax.scan(body, x, params["layers"])
    x = _norm(bc, x, params["final_norm"], params.get("final_norm_b"))
    cls_out = x[:, 0].astype(jnp.float32)
    return cls_out @ params["head"]["w"].astype(jnp.float32) + \
        params["head"]["b"]


def vit_loss(params: Params, batch: Dict[str, jnp.ndarray],
             cfg: ViTConfig) -> Tuple[jnp.ndarray, Dict[str, Any]]:
    logits = vit_forward(params, batch["image"], cfg)
    labels = batch["label"]
    logp = jax.nn.log_softmax(logits)
    loss = -jnp.take_along_axis(logp, labels[:, None],
                                axis=-1)[:, 0].mean()
    acc = (jnp.argmax(logits, axis=-1) == labels).mean()
    return loss, {"loss": loss, "accuracy": acc}


def make_vit_train_step(cfg: ViTConfig, optimizer):
    """(params, opt_state, batch) → (params, opt_state, metrics); jit
    (or pjit over a mesh with `pytree_shardings`) exactly like the LM
    train step."""
    import optax

    def step(params, opt_state, batch):
        (_, metrics), grads = jax.value_and_grad(
            vit_loss, has_aux=True)(params, batch, cfg)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, metrics

    return step
