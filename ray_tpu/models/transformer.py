"""Decoder-only transformer (GPT-2, Llama and latent-attention
mixture-of-experts families), TPU-first.

Design (idiomatic JAX, not a torch translation):

  * parameters are a plain pytree of jnp arrays; alongside it a matching
    ``params_axes`` tree of *logical axis* tuples feeds the sharding engine
    (`ray_tpu.parallel.sharding`) — TP/FSDP/PP are rules-table changes.
  * the layer stack is stacked weights scanned with ``lax.scan`` (fast
    compile, pipeline-parallel partitioning over the leading "layers"
    axis), with optional per-layer ``jax.checkpoint`` remat.
  * attention dispatches to the Pallas flash kernel on TPU
    (`ray_tpu.ops.attention`), ring attention a config switch; compute
    dtype bf16, params and softmax/norm statistics fp32.
  * a model DECLARES its layer pattern (`layer_runs`): consecutive runs of
    layers, each run one stacked tree.  Every layer loop (`_trunk` here,
    `generate._scan_cached`) goes through `scan_layer_runs`.  One run is
    ``params["layers"]``; leading dense layers before expert layers are a
    run of their own, ``params["dense_layers"]``.
  * the attention kind is a property of the configuration: ``"mha"``
    (MHA/GQA, keys and values of ``kv_heads x head_dim``) or ``"mla"``
    (latent attention: low-rank queries, ONE compressed key-value latent
    and one rotary key shared by all heads; `ops/latent_attention.py`).
  * the router kind decides the expert layer: ``"softmax"`` is the
    capacity einsum of `ops/moe.py` that trains over an ``ep`` mesh (and
    drops over capacity); ``"sigmoid"`` and ``"softmax_bias"``
    (`NO_DROP_ROUTERS`) are the bias-corrected choice that drops nothing
    (`routed_branch`, `ops/moe.routed_ffn`): sigmoid scores over exactly the
    experts that exist, normalised over the chosen, beside shared experts; or
    a softmax over the experts AND ``zero_experts`` IDENTITY experts that
    compute nothing, the raw scores the weights (one of them chosen adds its
    weight times the token's own row and joins no matmul).  Such a model may
    HOLD a share of its experts (``experts_held`` of ``n_experts`` from
    ``expert_offset``: one chip's part of an expert-parallel layer): the
    router keeps its width, the layer computes its own experts' part (and,
    every chip alike, the identity part), and nothing stands in for the rest.
    How many layers route and how many sums they report is the
    configuration's to say (`TransformerConfig.expert_layers`,
    ``reports_load``, ``load_counts``): the serve engine asks, and knows no
    router.
  * a SHORTCUT-CONNECTED layer (``shortcut_moe``) is TWO sublayers, each an
    attention operator and a dense feed-forward with one add each, and ONE
    routed branch wired across them: ``n_layers`` counts SUBLAYERS, so every
    count by layer (parameters, FLOPs, a cache's rows: TWO latents a published
    layer) and every cached program's layer body stand as they are;
    sublayers 0, 2, ... ALSO hold the router, its bias and the experts, in
    stacks over those sublayers alone (`_ROUTING_KEYS`, `_init_shortcut`),
    compute the routed sum off the normed input their dense feed-forward
    reads and HAND IT ON (`shortcut`; ``handed``'s ``routed``), and sublayers
    1, 3, ... add it with their feed-forward's output.  The layer loop runs a
    PAIR a scan step (`_scan_pairs`), so the branch stands in one body with
    the second attention and the first dense feed-forward, neither of which
    it depends on.
  * a head's width is its own (``head_size``), and what an attention
    block adds to the plain one is a property each: ``qk_norm``,
    ``attn_gate``, ``sandwich_norm``, ``embed_scale``.
  * a layer's KIND (``layer_kinds``: ``"window"`` | ``"full"`` | ``"conv"``
    | ``"eva"`` | ``"kda"`` | ``"ssm+full"`` | ``"mamba"`` | ``"gmu"`` |
    ``"cross"``, or of a model with an indexer ``"index"`` | ``"shared"``)
    may change from layer to layer and repeat inside a run: a
    window layer sees the last ``sliding_window`` positions (``rope_layers
    = "window"``: only those are rotated); a conv layer's operator is no
    attention at all but a gated short convolution (`ops/short_conv.py`);
    an ``"eva"`` layer sees its own BLOCK-ALIGNED window of
    ``sliding_window`` positions exactly and every earlier window through
    one pooled key and value a ``summary_chunk`` positions, under one
    softmax (`ops/eva_attention.py`; two weights a layer more,
    ``adaptive_phi`` and ``adaptive_mu_k``).  A model may have NO full
    layer at all.  A run that
    mixes the two operators holds BOTH weight sets, each stacked over its
    own layers only.  `layer_segments` cuts the runs where the kind
    changes; a segment that is part of a run loops over indices INTO the
    run's stacks, each operator's by its own counter (`_scan_part`; a
    slice would be a copy of the weights).
  * what an MHA/GQA head is may go BY THE LAYER'S KIND, each a property
    with the plain model as its default: a window layer's key-value heads
    (``window_kv_heads``: ``wk`` / ``wv`` of two shapes in one run, each
    stacked over its own kind's layers), its rotary base
    (``window_rope_base``), a learned sink in its softmax
    (``sink_kinds``); and for every kind a value head's width
    (``v_head_dim``), the rotated share of a head (``rope_fraction``) and
    a scale on the values (``value_scale``).

  * a LATENT-attention model has layer kinds of its own (``"index"`` |
    ``"shared"``, `SPARSE_KINDS`: learned sparse attention,
    `ops/sparse_index.py`): an INDEXING layer scores every earlier position
    with a few small heads of its own (``index_heads`` of
    ``index_head_dim``) and keeps the ``index_topk`` best; it and the
    ``"shared"`` layers behind it attend those alone.  So ONE LAYER
    COMPUTES A VALUE THAT LATER LAYERS CONSUME: the selection rides in the
    carry of `scan_layer_runs` from an indexing layer to the shared layers
    behind it, across the boundary of two runs too.  Indexer weights are
    stacked over the indexing layers only, and a served cache holds the
    indexer's keys on those layers alone: the fifth of its seven kinds of
    state (`models/generate.py`).  EVERY full layer may index for itself
    (no ``"shared"`` layer at all), and ``"window"`` layers may stand among
    them: the selection passes a window layer by untouched.

  * a latent layer's SIZES may go BY THE LAYER'S KIND, as an MHA/GQA head's
    do: a ``"window"`` layer of a latent-attention model has its own head
    count, query and key-value ranks and unturned head width
    (``window_heads``, ``window_q_lora_rank``, ``window_kv_lora_rank``,
    ``window_qk_nope_head_dim``; `TransformerConfig.latent_of`) and rotary
    base (``window_rope_base``), its weights in stacks of their own over the
    window layers alone (``*_win``, `latent_weights`: a run holds TWO LATENT
    SHAPES), its position ``t`` sees ``j`` with ``0 <= t - j <
    sliding_window``, and a served cache holds its latents in a RING of
    their own row width beside the full layers' rows (`models/generate.py`:
    the second state kind, over a latent).  All of its operator stands
    under the scope ``window_latent`` (`latent_scope`), AROUND the model's
    parts as ``ssm`` stands around a mixer's.  Two more properties of a
    latent block, each costing nothing at its default: a gate a HEAD
    (``head_gate``: one sigmoid of the block's input a head on the heads'
    output; ``attn_gate`` is MHA/GQA's, a value a channel) and a fixed
    multiplier on both latents after their norms (``latent_rescale``:
    ``sqrt(d_model / rank)``, each kind at its own ranks).

  * a ``"kda"`` layer's operator is no attention either but a GATED DELTA
    RULE (`ops/delta_rule.py`; `kda_operator`): queries, keys and values
    of ``kda_heads x kda_head_dim`` through a depthwise causal convolution
    of ``kda_conv_kernel`` taps, then a MATRIX of state a head in float32
    that every token decays channel by channel, corrects and reads.  Its
    weights are stacked over the KDA layers alone, beside the stacks of the
    attention layers of the same run (MHA/GQA or latent attention), and a
    served cache holds its state and its convolutions' last inputs: the
    sixth kind, whose arrays differ from the rest in TYPE.  A latent-
    attention model may project its queries directly (``q_lora_rank`` 0:
    no query latent, no query norm) and turn nothing (``pos_emb`` neither
    ``"rope"`` nor ``"learned"``: no position enters the model at all).

  * an ``"ssm+full"`` layer has TWO operators side by side off ONE norm, and
    what it adds to the residual is their SUM: a state-space mixer
    (`ssm_operator`, `ops/ssd.py`: values of ``ssm_heads x ssm_head_dim``,
    keys and queries of ``ssm_state`` shared by ``ssm_groups`` groups of
    heads, through one depthwise causal convolution with a bias, then a
    ``ssm_state x ssm_head_dim`` float32 matrix of state a head under an
    input-dependent step, a gate and a norm by group) AND full MHA/GQA
    attention, each with weights of its own (the mixer's stacked over the
    layers of this kind alone, attention's with the other attention
    layers').  Its parameter and FLOP counts are both operators'; a served
    cache holds for it a state WITHOUT positions and rows WITH them: the
    seventh kind beside the first (`models/generate.py`).

  * a DECODER THAT READS WHAT AN EARLIER LAYER MADE (`HANDING_KINDS`): a
    ``"mamba"`` layer's operator is a Mamba-1 SELECTIVE SCAN
    (`mamba_operator`, `ops/selective_scan.py`: ``mamba_expand x d_model``
    channels through a depthwise causal convolution with a bias, a step of
    rank ``mamba_dt_rank``, a float32 state of ``mamba_state`` columns a
    channel under a decay a channel A COLUMN, so no matmul chunk form as
    `ops/ssd.py`'s); a ``"gmu"`` layer's is a GATED MEMORY UNIT
    (`gmu_operator`: ``W_2 (m * silu(y W_1))``, ``m`` the scan output of the
    LAST mamba layer before it at the same position); a ``"cross"`` layer
    projects queries of its own and attends the keys and values of the LAST
    FULL layer before it.  So TWO values that one layer makes are consumed by
    layers behind it, carried down `scan_layer_runs` beside the stream as an
    indexer's choice is (`handed`, `hand_on`): the memory ``m`` and, in the
    plain form, the full layer's rows; the layer's index among all rides
    with them.  Each operator's weights are stacked over ITS layers alone
    (a cross layer has ``wq`` / ``wo`` with the attention layers' and no
    ``wk`` / ``wv``).  A gmu and a cross layer hold NO state
    (`READER_KINDS`): a served cache has nothing for them, and where they
    are the model's last layers (`TransformerConfig.stateless_tail`) a
    cached program that wants one row's logits runs them on that row alone
    (`models/generate.py`).  `check_kinds` refuses a reader with no maker
    before it.

  * an MHA/GQA block may be DIFFERENTIAL, a property (``diff_attn``) that
    costs nothing when off: query heads ``(2p, 2p + 1)`` are a PAIR, the
    pair's two softmax maps are taken against the two halves of ONE key row
    (``n_kv_heads`` counts key-value PAIRS: a row is `key_dim` = 2 x
    ``head_dim`` wide, its value as wide) and subtracted under a norm:
    ``(1 - lambda_init) rmsnorm(o_1 - lambda o_2)``, ``lambda = exp(lq1 .
    lk1) - exp(lq2 . lk2) + lambda_init``, ``lambda_init = 0.8 - 0.6 exp(-0.3
    l)`` by the layer's index among all (`diff_pairs`, scope ``diff``).  It
    runs as ONE grouped-query attention over rows of `key_dim`: head 2p's
    query is padded to ``[q | 0]``, head 2p + 1's to ``[0 | q]``
    (`_pair_queries`), the scores scaled by one head's ``head_dim ** -0.5``
    (`attention_scale`), so every implementation (flash, reference, the cache
    kernels) takes it as it is and a cached row is fetched once for both
    maps.  ``attn_bias`` puts biases on the four projections.

  * a model may state FIXED MULTIPLIERS at named places (muP), each a field
    that defaults to 1 and costs nothing there: the embedding's
    (``embed_scale``), the logits' (``logit_scale``), attention's input,
    keys and output (``attn_in_scale``, ``key_scale``, ``attn_out_scale``),
    a feed-forward's gate and output (``ffn_gate_scale``,
    ``ffn_out_scale``), a state-space mixer's input, output and the five
    segments of its input projection (``ssm_in_scale``, ``ssm_out_scale``,
    ``ssm_scales``).

  * what a block, the stream and the head are may differ too, each a
    property with the plain model as its default: an RMSNorm that
    multiplies by ``1 + g`` (``norm_unit_offset``), a residual stream held
    in float32 while the matmuls run in ``dtype`` (``fp32_residual``), a
    head accumulated in float32 (``fp32_logits``), and an unembedding of
    ``pred_heads x vocab_size`` columns (head p predicts the token ``1 +
    p`` positions on; `lm_loss` is the heads' mean, a served token head
    0's).

Configs: ``TransformerConfig.gpt2()`` (learned positions, GELU, LayerNorm)
and ``TransformerConfig.llama()`` (RoPE, SwiGLU, RMSNorm, GQA).
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..ops import latent_attention as mla
from ..ops import sparse_index
from ..ops.attention import multi_head_attention
from ..ops.eva_attention import eva_attention
from ..ops.flash_attention import FLASH_LSE, FLASH_OUT
from ..ops.norms import layernorm, rmsnorm
from ..ops.rotary import apply_rotary, rotary_angles

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 50304          # GPT-2 vocab padded to a 128 multiple
    d_model: int = 768
    n_layers: int = 12
    n_heads: int = 12
    n_kv_heads: Optional[int] = None  # None → MHA
    d_ff: Optional[int] = None        # None → 4*d_model (gelu) / 8/3 (swiglu)
    max_seq_len: int = 1024
    pos_emb: str = "learned"          # "learned" | "rope" | "none": no
    #   position enters the model (its state layers give it order)
    activation: str = "gelu"          # "gelu" | "swiglu"
    norm: str = "layernorm"           # "layernorm" | "rmsnorm"
    tie_embeddings: bool = True
    rope_base: float = 10000.0
    dtype: Any = jnp.bfloat16         # activation/compute dtype
    param_dtype: Any = jnp.float32
    attention_impl: str = "auto"      # "auto"|"flash"|"reference"|"ring"
    causal: bool = True               # False → bidirectional (encoders)
    remat: Any = True                 # False | True (full: a layer keeps
    #   its input and, where attention is the flash kernel, the kernel's
    #   output and row statistics; all else is recomputed) | "dots":
    #   saves matmul outputs and recomputes only elementwise ops in the
    #   backward pass (most of full remat's memory win, no extra MXU work)
    embed_impl: str = "gather"        # "gather" | "one_hot" (MXU-matmul
    #   embedding: gather-bwd is a serialized scatter-add on TPU)
    norm_remat: bool = False          # recompute layernorm/rmsnorm in bwd
    #   instead of saving their fp32 intermediates ([b, s, d] x 2 a layer)
    loss_chunk: int = 0               # >0 → chunked cross entropy: logits
    #   materialize [b, chunk, vocab] at a time (rematerialized in bwd)
    # -- pipeline parallelism (SURVEY §2.4 row 3; parallel/pipeline.py) -----
    pp_stages: int = 1                # >1 → GPipe schedule over mesh "pp"
    pp_microbatches: Optional[int] = None  # None → pp_stages
    # -- mixture of experts (SURVEY §2.4 row 5; ops/moe.py) -----------------
    n_experts: int = 0                # 0 → dense FFN
    expert_top_k: int = 2
    capacity_factor: float = 2.0
    router_aux_weight: float = 0.01   # Switch load-balancing loss weight
    router: str = "softmax"           # "softmax": capacity einsum, aux
    #   loss, drops over capacity (trains over the ep mesh) | "sigmoid":
    #   choice by sigmoid score + correction bias, weights the chosen
    #   scores normalised, no token dropped (ops/moe.routed_ffn) |
    #   "softmax_bias": a softmax over the experts AND ``zero_experts``
    #   identity outputs, choice by score + correction bias, weights the raw
    #   scores times the scaling factor, no token dropped (the same layer)
    moe_d_ff: Optional[int] = None    # a routed expert's width (None → ff_dim)
    n_shared_experts: int = 0         # always-on experts of width moe_d_ff
    routed_scaling_factor: float = 1.0
    first_dense_layers: int = 0       # leading layers with a dense FFN of
    #   width ff_dim before the expert layers (only with n_experts > 0)
    # -- latent attention (ops/latent_attention.py) -------------------------
    attention: str = "mha"            # "mha" | "mla"
    q_lora_rank: int = 0              # 0: queries projected directly
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    norm_eps: Optional[float] = None  # None → the norm's own default
    #   (rmsnorm 1e-6, layernorm 1e-5)
    # -- what a latent layer is, by the layer's kind (`latent_of`) ------------
    window_heads: Optional[int] = None      # a "window" latent layer's heads,
    window_q_lora_rank: Optional[int] = None    # ... its query latent,
    window_kv_lora_rank: Optional[int] = None   # ... its key-value latent
    window_qk_nope_head_dim: Optional[int] = None   # ... and the part of a
    #   head's query that is not turned (None → the model's, each): a model
    #   that states one holds the window layers' weights in stacks of their
    #   own (``*_win``) and their latents in a ring of their own row width
    head_gate: bool = False           # a latent layer's heads' output times
    #   sigmoid(y W_g), ONE value a head (``attn_gate`` is MHA/GQA's, a
    #   value a channel)
    latent_rescale: bool = False      # both latents times sqrt(d_model /
    #   rank) after their norm, each kind's at its own rank
    # -- what an MHA/GQA block may add to the plain one ---------------------
    head_size: Optional[int] = None   # a head's width (None → d_model //
    #   n_heads); queries are n_heads * head_size wide, not d_model
    qk_norm: bool = False             # RMS norm over each head's q and k
    attn_gate: bool = False           # heads' output * sigmoid(y W_g)
    sandwich_norm: bool = False       # norms after attention and FFN too
    embed_scale: float = 1.0          # multiplies the token embedding
    # -- kinds of layer mixed -----------------------------------------------
    layer_kinds: Optional[Tuple[str, ...]] = None  # a layer "window" |
    #   "full" | "conv" | "eva" | "kda" | "ssm+full" | "mamba" | "gmu" |
    #   "cross", or of a model with an indexer "index" | "shared", in model
    #   order (None → all full); may repeat inside a run
    conv_kernel: int = 3              # a conv layer's taps; its state is
    #   the last conv_kernel - 1 inputs of the convolution a sequence
    sliding_window: int = 0           # a window layer's position i sees
    #   j <= i with i - j < sliding_window
    rope_layers: str = "all"          # "all" | "window": which kinds of
    #   layer a rope model rotates (the others carry no position at all)
    window_chunk: int = 128           # the widest run of new tokens one
    #   cached program may feed: a window layer's cache is a ring of
    #   sliding_window + window_chunk rows (models/generate.py)
    # -- a share of the experts (one chip of an expert-parallel layer) ------
    experts_held: Optional[int] = None  # None → all n_experts
    expert_offset: int = 0            # the first expert held
    zero_experts: int = 0             # IDENTITY experts behind the n_experts
    #   in a "softmax_bias" router's outputs: one of them chosen adds its
    #   weight times the token's own row and computes nothing
    shortcut_moe: bool = False        # a published layer is TWO sublayers
    #   (``n_layers`` counts SUBLAYERS, each latent or MHA/GQA attention and
    #   a dense feed-forward of ``ff_dim``) and ONE routed branch beside
    #   them: sublayers 0, 2, 4, ... ALSO hold a router and the experts
    #   (stacked over those sublayers alone), compute the routed sum off the
    #   normed input their dense feed-forward reads and HAND IT ON; sublayers
    #   1, 3, ... add it with their own feed-forward's output
    # -- what an MHA/GQA head may differ in, by the layer's kind -------------
    # (``v_head_dim`` above, where set, is an MHA/GQA value head's width too)
    window_kv_heads: Optional[int] = None  # a window layer's key-value
    #   heads (None → n_kv_heads): where they differ a run holds wk / wv of
    #   two shapes, each stacked over its own kind's layers
    window_rope_base: Optional[float] = None  # a window layer's rotary
    #   base (None → rope_base)
    rope_fraction: float = 1.0        # the share of a head's FIRST dims that
    #   is rotated (truncated to a whole number; the rest carry no position)
    sink_kinds: Tuple[str, ...] = ()  # the kinds of layer whose softmax has
    #   a learned logit a query head in its denominator that takes no value
    value_scale: float = 1.0          # multiplies the value projection
    # -- attention through chunk summaries (ops/eva_attention.py) -----------
    summary_chunk: int = 0            # an "eva" layer's position sees its
    #   own BLOCK of sliding_window positions (block-aligned, not sliding)
    #   exactly and every earlier block through ONE pooled key and value a
    #   summary_chunk positions, under one softmax; two weights a layer
    #   more (adaptive_phi, adaptive_mu_k: [kv_heads, head_dim])
    # -- learned sparse attention (ops/sparse_index.py; latent attention) ----
    index_heads: int = 0              # an indexing layer's scoring heads
    index_head_dim: int = 0           # ... their width: ONE key a position
    #   of this width is what an indexing layer caches beside its latent
    index_topk: int = 0               # positions a query attends (0: no
    #   indexer): ``layer_kinds`` is then "index" (scores, chooses, attends
    #   its choice) | "shared" (attends the choice of the nearest indexing
    #   layer before it; holds no indexer weights) for every layer
    # -- a gated delta rule in attention's place (ops/delta_rule.py) ---------
    kda_heads: int = 0                # a "kda" layer's heads (0: none) ...
    kda_head_dim: int = 0             # ... of this width, key and value: a
    #   sequence carries kda_heads x kda_head_dim x kda_head_dim float32
    kda_conv_kernel: int = 4          # taps of the depthwise convolution its
    #   queries, keys and values go through (SiLU after)
    kda_gate_rank: int = 0            # rank of the decay's and the output
    #   gate's two-step projections
    # -- a state-space mixer BESIDE attention (ops/ssd.py) -------------------
    ssm_heads: int = 0                # an "ssm+full" layer's state heads ...
    ssm_head_dim: int = 0             # ... of this width (the mixer is
    #   ssm_heads x ssm_head_dim wide, whatever d_model)
    ssm_state: int = 0                # a key's and a query's width: a
    #   sequence carries ssm_heads x ssm_state x ssm_head_dim float32
    ssm_groups: int = 1               # groups of heads that share one key
    #   and one query; the gated norm runs over each group's channels
    ssm_conv_kernel: int = 4          # taps of the depthwise convolution
    #   (with a bias, SiLU after) over values, keys and queries
    # -- fixed multipliers at named places (1: none, and no instruction) -----
    logit_scale: float = 1.0          # multiplies the logits
    attn_in_scale: float = 1.0        # an MHA/GQA block's normed input
    attn_out_scale: float = 1.0       # ... and what it adds to the residual
    key_scale: float = 1.0            # its keys, before they are turned
    ffn_gate_scale: float = 1.0       # a dense feed-forward's gate
    #   projection, before the activation
    ffn_out_scale: float = 1.0        # ... and what it adds to the residual
    ssm_in_scale: float = 1.0         # a state-space mixer's normed input
    ssm_out_scale: float = 1.0        # ... and what it adds to the residual
    ssm_scales: Tuple[float, ...] = (1.0,) * 5  # its input projection by
    #   segment: gate | values | keys | queries | step
    # -- what a block, the stream and the head may differ in -----------------
    norm_unit_offset: bool = False    # an RMSNorm multiplies by 1 + g
    fp32_residual: bool = False       # the residual stream is float32 (the
    #   norms hand the matmuls cfg.dtype), not cfg.dtype
    fp32_logits: bool = False         # the served head accumulates float32
    pred_heads: int = 1               # the unembedding has pred_heads x
    #   vocab_size columns: head p predicts the token at t + 1 + p, and a
    #   served token is drawn from head 0
    # -- layers that read what an EARLIER layer made (ops/selective_scan.py) --
    mamba_state: int = 0              # a "mamba" layer's state columns (0:
    #   none): a sequence carries mamba_state x mamba_inner float32 a layer
    mamba_expand: int = 2             # its channels over d_model
    mamba_conv_kernel: int = 4        # taps of its depthwise convolution
    #   (with a bias, SiLU after)
    mamba_dt_rank: int = 0            # rank of the step's two-step projection
    diff_attn: bool = False           # an MHA/GQA block subtracts two softmax
    #   maps under a norm: query heads (2p, 2p + 1) are a PAIR; n_kv_heads
    #   counts key-value PAIRS, a pair's two keys of head_dim side by side
    #   ONE cached row of 2 x head_dim and its value as wide
    attn_bias: bool = False           # biases on an MHA/GQA block's query,
    #   key, value and output projections

    @property
    def head_dim(self) -> int:
        return self.head_size or self.d_model // self.n_heads

    @property
    def stream_dtype(self):
        """What the residual stream is held in."""
        return jnp.float32 if self.fp32_residual else self.dtype

    @property
    def logit_size(self) -> int:
        """Columns of the unembedding: every prediction head's."""
        return self.vocab_size * self.pred_heads

    @property
    def n_experts_held(self) -> int:
        return self.experts_held or self.n_experts

    @property
    def expert_layers(self) -> int:
        """The layers that ROUTE (hold a router and routed experts): every
        layer of the run behind the leading dense ones, or of a
        shortcut-connected model every second sublayer."""
        if not self.n_experts:
            return 0
        return self.n_layers // 2 if self.shortcut_moe \
            else dict(self.layer_runs)["layers"]

    @property
    def reports_load(self) -> bool:
        """Whether the expert layers drop nothing and say what they routed
        (`ops.moe.Load`): the serve engine's ``moe`` counters."""
        return bool(self.n_experts) and self.router in NO_DROP_ROUTERS

    @property
    def load_counts(self) -> int:
        """How many of `ops.moe.Load`'s sums a layer hands the loop: the
        identity pairs only where the router has identity experts."""
        return 4 if self.zero_experts else 3

    @property
    def expert_stacks(self) -> Tuple[str, ...]:
        """The routed experts' stacks in a run's tree: beside a dense
        feed-forward of the same layer they have names of their own."""
        return _SHORTCUT_STACKS if self.shortcut_moe else _EXPERT_STACKS

    @property
    def kinds(self) -> Tuple[str, ...]:
        """Each layer's kind, in model order."""
        return self.layer_kinds or ("full",) * self.n_layers

    def rotates(self, kind: str) -> bool:
        """Whether a layer of this kind turns its queries and keys."""
        return self.pos_emb == "rope" and (
            self.rope_layers == "all" or kind == "window")

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads or self.n_heads

    def kv_heads_of(self, kind: str) -> int:
        """Key-value heads of an attention layer of this kind."""
        return self.window_kv_heads if kind == "window" \
            and self.window_kv_heads else self.kv_heads

    @property
    def split_kv(self) -> bool:
        """Whether window and full layers' key and value projections have
        different shapes (and so stacks of their own: ``wk_win``,
        ``wv_win`` beside ``wk``, ``wv``)."""
        return self.kv_heads_of("window") != self.kv_heads

    @property
    def key_dim(self) -> int:
        """Width of an MHA/GQA key ROW as cached and attended: a head's, or
        a differential pair's two keys side by side."""
        return self.head_dim * (2 if self.diff_attn else 1)

    @property
    def value_dim(self) -> int:
        """An MHA/GQA value head's width (the key row's unless stated)."""
        return self.v_head_dim or self.key_dim

    @property
    def out_heads(self) -> int:
        """Heads the output projection reads: the query heads, or their
        pairs where two maps are subtracted."""
        return self.n_heads // 2 if self.diff_attn else self.n_heads

    @property
    def mamba_inner(self) -> int:
        """A ``"mamba"`` layer's channels."""
        return self.mamba_expand * self.d_model

    @property
    def hands_down(self) -> bool:
        """Whether the layer loop carries values that one layer makes for the
        layers behind it (`handed`): a memory, a full layer's rows, the
        layer's index."""
        return self.diff_attn or self.shortcut_moe \
            or bool(set(self.kinds) & set(HANDING_KINDS))

    @property
    def stateless_tail(self) -> int:
        """The layers BEHIND the last one that holds state of its own: the
        trailing `READER_KINDS` layers, which write nothing a later token
        reads (a cached program that wants one row's logits runs them on
        that row alone)."""
        n = 0
        while n < self.n_layers and self.kinds[-1 - n] in READER_KINDS:
            n += 1
        return n

    @property
    def window_latent(self) -> bool:
        """Whether the model has window layers over a LATENT cache: their
        weights are stacks of their own and their latents a ring."""
        return self.attention == "mla" and "window" in self.kinds

    def latent_of(self, kind: str) -> "TransformerConfig":
        """The configuration whose latent sizes (``n_heads``,
        ``q_lora_rank``, ``kv_lora_rank``, ``qk_nope_head_dim``) are those
        of a latent layer of this kind: the model's own but for a window
        layer that states its own."""
        if kind != "window" or self.attention != "mla":
            return self
        own = {name: getattr(self, "window_" + field)
               for name, field in (("n_heads", "heads"),
                                   ("q_lora_rank", "q_lora_rank"),
                                   ("kv_lora_rank", "kv_lora_rank"),
                                   ("qk_nope_head_dim", "qk_nope_head_dim"))}
        return dataclasses.replace(
            self, **{k: v for k, v in own.items() if v is not None})

    def latent_scales(self, kind: str) -> Tuple[float, float]:
        """The fixed multipliers on a latent layer's (query latent,
        key-value latent) after their norms: 1 unless the model rescales."""
        if not self.latent_rescale:
            return 1.0, 1.0
        ck = self.latent_of(kind)
        return (math.sqrt(self.d_model / ck.q_lora_rank)
                if ck.q_lora_rank else 1.0,
                math.sqrt(self.d_model / ck.kv_lora_rank))

    def rope_base_of(self, kind: str) -> float:
        return self.window_rope_base if kind == "window" \
            and self.window_rope_base else self.rope_base

    @property
    def rope_dim(self) -> int:
        """Width of what the rotary angles turn: a whole head or its
        first ``rope_fraction``, or the rotary part of a latent-attention
        head."""
        return self.qk_rope_head_dim if self.attention == "mla" \
            else int(self.rope_fraction * self.head_dim) \
            if self.rope_fraction != 1.0 else self.head_dim

    @property
    def expert_ff_dim(self) -> int:
        return self.moe_d_ff or self.ff_dim

    @property
    def layer_runs(self) -> Tuple[Tuple[str, int], ...]:
        """The declared layer pattern: (key of the run's stacked tree in
        ``params``, layers in the run), in model order."""
        lead = self.first_dense_layers if self.n_experts else 0
        if lead:
            return (("dense_layers", lead), ("layers", self.n_layers - lead))
        return (("layers", self.n_layers),)

    @property
    def layer_segments(self) -> Tuple[Tuple[str, int, int, str], ...]:
        """`layer_runs` cut where the attention kind changes: (run, first
        layer within the run, layers, kind), in model order.  A model of
        one kind has one segment a run."""
        out, first = [], 0
        for run, n in self.layer_runs:
            kinds = self.kinds[first:first + n]
            start = 0
            for i in range(1, n + 1):
                if i == n or kinds[i] != kinds[start]:
                    out.append((run, start, i - start, kinds[start]))
                    start = i
            first += n
        return tuple(out)

    @property
    def ff_dim(self) -> int:
        if self.d_ff is not None:
            return self.d_ff
        if self.activation == "swiglu":
            # Llama convention: 8/3 * d, rounded up to a 256 multiple
            return ((int(8 * self.d_model / 3) + 255) // 256) * 256
        return 4 * self.d_model

    # -- presets (sizes follow the public GPT-2/Llama papers) ---------------
    @staticmethod
    def gpt2(size: str = "small", **kw) -> "TransformerConfig":
        dims = {"small": (768, 12, 12), "medium": (1024, 24, 16),
                "large": (1280, 36, 20), "xl": (1600, 48, 25)}[size]
        d, l, h = dims
        return TransformerConfig(
            vocab_size=50304, d_model=d, n_layers=l, n_heads=h,
            max_seq_len=kw.pop("max_seq_len", 1024), pos_emb="learned",
            activation="gelu", norm="layernorm", tie_embeddings=True, **kw)

    @staticmethod
    def llama(size: str = "1b", **kw) -> "TransformerConfig":
        dims = {  # d_model, layers, heads, kv_heads, d_ff, vocab
            "tiny": (512, 4, 8, 4, 1408, 32000),
            "1b": (2048, 16, 32, 8, 8192, 128256),
            "3b": (3072, 28, 24, 8, 8192, 128256),
            "8b": (4096, 32, 32, 8, 14336, 128256),
        }[size]
        d, l, h, hk, ff, v = dims
        return TransformerConfig(
            vocab_size=v, d_model=d, n_layers=l, n_heads=h, n_kv_heads=hk,
            d_ff=ff, max_seq_len=kw.pop("max_seq_len", 4096),
            pos_emb="rope", activation="swiglu", norm="rmsnorm",
            tie_embeddings=False, **kw)

    @staticmethod
    def tiny(**kw) -> "TransformerConfig":
        """Test-sized model that still exercises every code path."""
        defaults = dict(vocab_size=256, d_model=64, n_layers=2, n_heads=4,
                        n_kv_heads=2, max_seq_len=128, pos_emb="rope",
                        activation="swiglu", norm="rmsnorm",
                        tie_embeddings=False, remat=False)
        defaults.update(kw)
        return TransformerConfig(**defaults)


def _attn_matmul_params(cfg: TransformerConfig, kind: str = "full") -> int:
    d, h = cfg.d_model, cfg.n_heads
    if cfg.attention == "mla":
        cfg = cfg.latent_of(kind)   # the kind's own latent sizes
        h, nope, rope, v = (cfg.n_heads, cfg.qk_nope_head_dim,
                            cfg.qk_rope_head_dim, cfg.v_head_dim)
        ql = cfg.q_lora_rank        # no query latent: one projection
        return ((d * ql + ql * h * (nope + rope) if ql
                 else d * h * (nope + rope))
                + d * (cfg.kv_lora_rank + rope)
                + cfg.kv_lora_rank * h * (nope + v) + h * v * d
                + (d * h if cfg.head_gate else 0))
    hd, vd, hk = cfg.head_dim, cfg.value_dim, cfg.kv_heads_of(kind)
    gate = d * h * vd if cfg.attn_gate else 0
    # (a "cross" layer projects queries alone: it reads a full layer's rows)
    kv = 0 if kind == "cross" else d * hk * (cfg.key_dim + vd)
    return d * h * hd + kv + cfg.out_heads * vd * d + gate


def _run_matmul_params(cfg: TransformerConfig, run: str, active: bool) -> int:
    """Matmul parameters of the FEED-FORWARD of one layer of the run ``run``
    of `layer_runs`; for expert layers ``active`` counts only the top-k
    routed experts a token visits (the FLOP count) beside the shared ones,
    ``active=False`` every expert held (the memory count)."""
    d = cfg.d_model
    per = 3 if cfg.activation == "swiglu" else 2
    if cfg.n_experts and run == "layers" and not cfg.shortcut_moe:
        mlp = _routed_matmul_params(cfg, active)
    else:
        mlp = d * cfg.ff_dim * per
    return mlp


def _matmul_params(cfg: TransformerConfig, active: bool) -> int:
    """Matmul parameters of all layers, over the declared pattern: each
    layer's feed-forward and its kind's operator."""
    return sum(n * _run_matmul_params(cfg, run, active)
               for run, n in cfg.layer_runs) + (
        cfg.expert_layers * _routed_matmul_params(cfg, active)
        if cfg.shortcut_moe else 0) + sum(
        4 * cfg.d_model ** 2 if kind == "conv"      # in [d, 3d], out [d, d]
        else _kda_matmul_params(cfg) if kind == "kda"
        else _mamba_matmul_params(cfg) if kind == "mamba"
        else 2 * cfg.d_model * cfg.mamba_inner if kind == "gmu"
        else _attn_matmul_params(cfg, kind)
        + (_ssm_matmul_params(cfg) if kind in SSM_KINDS else 0)
        for kind in cfg.kinds) \
        + cfg.kinds.count("index") * _indexer_matmul_params(cfg)


def _kda_matmul_params(cfg: TransformerConfig) -> int:
    """A KDA layer's projections: queries, keys and values in, the heads
    out, the decay's and the gate's two steps, the step size a head."""
    d, e, r = cfg.d_model, cfg.kda_heads * cfg.kda_head_dim, cfg.kda_gate_rank
    return 4 * d * e + 2 * (d * r + r * e) + d * cfg.kda_heads


def _kda_state_size(cfg: TransformerConfig) -> int:
    """Floats of delta state a sequence, summed over the KDA layers."""
    return cfg.kinds.count("kda") * cfg.kda_heads * cfg.kda_head_dim ** 2


def _indexer_matmul_params(cfg: TransformerConfig) -> int:
    """An indexing layer's three projections: queries from the query
    latent, one key and the head weights from the block's input."""
    return cfg.q_lora_rank * cfg.index_heads * cfg.index_head_dim \
        + cfg.d_model * (cfg.index_head_dim + cfg.index_heads)


def _attn_flops_dim(cfg: TransformerConfig, kind: str = "full") -> int:
    """Width, summed over heads, of one query-key product plus one
    probability-value product, halved: what `flops_per_token` multiplies
    by positions (``n_heads * head_dim`` where both are one size); a latent
    layer's at its kind's own sizes."""
    if cfg.attention == "mla":
        cfg = cfg.latent_of(kind)
        return cfg.n_heads * (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
                              + cfg.v_head_dim) // 2
    return cfg.n_heads * (cfg.head_dim + cfg.value_dim) // 2


def _attention_work(cfg: TransformerConfig, width_of, context_len: float,
                    windows: int = 1) -> float:
    """``width_of(kind)`` x the positions a query attends (`_attended`),
    summed over the layers, a kind at a time: a window latent layer's heads
    are its own."""
    return sum(width_of(kind) * _attended(cfg, context_len, windows, (kind,))
               for kind in sorted(set(cfg.kinds)))


def _attended(cfg: TransformerConfig, context_len: float,
              windows: int = 1, kinds: Optional[Tuple[str, ...]] = None
              ) -> float:
    """Positions one query at depth ``context_len`` attends, summed over
    the layers (of the kinds ``kinds``; None: all): a window layer stops at
    ``windows`` x its window (2 where the caller halves the sum for a causal
    sequence's mean)."""
    def rows(kind):
        if kind == "window":
            return min(context_len, windows * cfg.sliding_window)
        if kind == "eva":   # at most its block, and the chunks before it
            return min(context_len, cfg.sliding_window) \
                + context_len / cfg.summary_chunk
        if kind in SPARSE_KINDS:    # the chosen positions
            return min(context_len, windows * cfg.index_topk)
        return context_len

    # (a conv and a KDA layer attend nothing; a layer with a state-space
    # mixer BESIDE attention attends as a full one)
    return sum(rows(kind) for kind in cfg.kinds if kind not in _NO_ATTENTION
               and (kinds is None or kind in kinds))


def count_params(cfg: TransformerConfig) -> int:
    d = cfg.d_model
    norms = 2 * d * (2 if cfg.norm == "layernorm" else 1)
    if cfg.sandwich_norm:            # two more scales, no bias
        norms += 2 * d
    n_conv, n_kda = cfg.kinds.count("conv"), cfg.kinds.count("kda")
    e = cfg.kda_heads * cfg.kda_head_dim    # a KDA layer's own: three
    #   convolutions, a decay a head, its bias, the heads' norm
    kda = 3 * e * cfg.kda_conv_kernel + cfg.kda_heads + e + cfg.kda_head_dim
    def own(k):         # an attention layer's: the latents' norms, or a
        #   head's query and key norms, its biases, a differential pair's
        ck = cfg.latent_of(k)
        return ck.q_lora_rank + ck.kv_lora_rank if cfg.attention == "mla" \
            else (2 * cfg.head_dim if cfg.qk_norm else 0) \
            + _attn_own_params(cfg, k)

    layers = _matmul_params(cfg, active=False) + cfg.n_layers * norms \
        + sum(own(k) for k in cfg.kinds if k not in _NO_ATTENTION) \
        + cfg.kinds.count("mamba") * _mamba_own_params(cfg) \
        + n_kda * kda \
        + sum(k in SSM_KINDS for k in cfg.kinds) * _ssm_own_params(cfg) \
        + n_conv * d * cfg.conv_kernel \
        + sum(k in cfg.sink_kinds for k in cfg.kinds) * cfg.n_heads \
        + cfg.kinds.count("eva") * 2 * cfg.kv_heads * cfg.head_dim \
        + cfg.kinds.count("index") * 2 * cfg.index_head_dim  # the key's norm
    if cfg.reports_load:    # the correction bias, an output of the router
        layers += cfg.expert_layers * (cfg.n_experts + cfg.zero_experts)
    emb = cfg.vocab_size * d
    if cfg.pos_emb == "learned":
        emb += cfg.max_seq_len * d
    head = 0 if cfg.tie_embeddings else cfg.logit_size * d
    final = d * (2 if cfg.norm == "layernorm" else 1)
    return layers + emb + head + final


def flops_per_token(cfg: TransformerConfig, seq_len: int) -> float:
    """Training FLOPs/token: 6*N_active_matmul + causal attention term."""
    unembed = cfg.logit_size * cfg.d_model  # tied or not, the logits matmul runs
    n_matmul = _matmul_params(cfg, active=True) + unembed
    # qk+pv over the visible window: half the positions when causal,
    # all of them for bidirectional encoders (causal=False)
    attn_factor = 6 if cfg.causal else 12
    attn = _attention_work(
        cfg, lambda kind: attn_factor * _attn_flops_dim(cfg, kind), seq_len,
        2 if cfg.causal else 1)
    # an indexing layer's heads meet every position's ONE key (no values)
    attn += attn_factor // 2 * _index_flops_dim(cfg) * seq_len
    # a delta state's decay, read, correction and write a token: 7 a float
    return 6 * n_matmul + attn + 3 * 7 * _kda_state_size(cfg) \
        + 3 * _SSM_STATE_OPS * _ssm_state_size(cfg) \
        + 3 * _MAMBA_STATE_OPS * _mamba_state_size(cfg)


def _index_flops_dim(cfg: TransformerConfig) -> int:
    """Width, summed over heads and indexing layers, of the indexer's one
    product a query a position."""
    return cfg.kinds.count("index") * cfg.index_heads * cfg.index_head_dim


def decode_flops_per_token(cfg: TransformerConfig,
                           context_len: int) -> float:
    """Inference forward FLOPs for ONE token at cache position
    ``context_len``: 2*N_active_matmul for the weight matmuls plus the
    attention layers' reads against the KV cache (qk^T and probs·v, 2
    FLOPs per MAC each, over every cached position)."""
    n_matmul = _matmul_params(cfg, active=True) \
        + cfg.logit_size * cfg.d_model   # unembed logits matmul
    def per_pos(kind):
        if cfg.attention == "mla":
            # absorbed: every head's query meets the cached latent row (and
            # its rotary key), and the probabilities the latent again
            ck = cfg.latent_of(kind)
            return ck.n_heads * (2 * ck.kv_lora_rank + ck.qk_rope_head_dim)
        return cfg.n_heads * (cfg.head_dim + cfg.value_dim)

    # (a KDA layer's cost does not grow with the context)
    return 2 * n_matmul + _attention_work(
        cfg, lambda kind: 2 * per_pos(kind), context_len) \
        + 2 * _index_flops_dim(cfg) * context_len + 7 * _kda_state_size(cfg) \
        + _SSM_STATE_OPS * _ssm_state_size(cfg) \
        + _MAMBA_STATE_OPS * _mamba_state_size(cfg)


def engine_flops_table(cfg: TransformerConfig, max_len: int) -> dict:
    """Analytic FLOPs-per-token for each of the serve engine's jitted
    programs (the dispatch profiler's MFU numerators), evaluated at the
    mid-stream cache position ``max_len // 2``.  Pure-copy programs (cache
    insert/gather) are 0: the profiler reports no MFU for them."""
    mid = max(1, max_len // 2)
    target = decode_flops_per_token(cfg, mid)
    table = {
        "decode_step": target,
        "prefill_chunk": target,   # per prompt token, same forward
        "cache_insert": 0.0,
        "prefix_gather": 0.0,
    }
    return table


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _init_run(keys, cfg: TransformerConfig, run: str, L: int
              ) -> Tuple[Params, Params]:
    """One run of `layer_runs`: ``L`` layers as one stacked tree (leading
    "layers" axis) and its logical axes; ``keys`` an iterator of keys, one
    drawn a weight.  An operator's weights are stacked over ITS layers of
    the run only (`stack_kinds`, `kind_layers`): attention's ``La``, a
    conv's ``Lc``; where window and full layers differ in key-value heads
    (`TransformerConfig.split_kv`) each kind's ``wk`` / ``wv`` over its
    own layers, and the sinks over the layers that have one."""
    La, Lc = operator_layers(cfg, run)
    Lk = kind_layers(cfg, run, ("kda",))
    d, hd, vd, h, ff = (cfg.d_model, cfg.head_dim, cfg.value_dim,
                        cfg.n_heads, cfg.ff_dim)
    pt = cfg.param_dtype
    p: Params = {"attn_norm": _unit_scale(cfg, (L, d)),
                 "mlp_norm": _unit_scale(cfg, (L, d))}
    ax: Params = {"attn_norm": ("layers", "embed"),
                  "mlp_norm": ("layers", "embed")}

    def add(name, shape, fan_in, axes, n=L, key=None):
        p[name] = jax.random.normal(next(keys) if key is None else key,
                                    (n,) + shape, pt) / math.sqrt(fan_in)
        ax[name] = ("layers",) + axes

    if Lc:      # the gated short convolution (ops/short_conv.py)
        k = cfg.conv_kernel
        add("conv_in", (d, 3 * d), d, ("embed", None), Lc)
        add("conv_w", (d, k), k, (None, None), Lc)
        add("conv_out", (d, d), d, (None, "embed"), Lc)
    if Lk:      # the gated delta rule (ops/delta_rule.py): ONE of the
        # run's keys, so a model draws its other weights as it did
        _init_kda(add, p, ax, cfg, Lk, next(keys))
    if kind_layers(cfg, run, SSM_KINDS):    # the state-space mixer, likewise
        _init_ssm(add, p, ax, cfg, kind_layers(cfg, run, SSM_KINDS),
                  next(keys))
    if kind_layers(cfg, run, _MEMORY_LAYERS):   # a selective scan and the
        # gated units that read its memory, likewise
        _init_memory(add, p, ax, cfg, run, next(keys))
    if La and cfg.attention == "mla":
        n_win = kind_layers(cfg, run, ("window",)) if cfg.window_latent else 0
        ql = cfg.q_lora_rank
        _init_latent(add, p, ax, cfg, La - n_win, keys)
        n_idx = kind_layers(cfg, run, ("index",))
        if n_idx:       # the indexer: over the indexing layers only
            hi, di = cfg.index_heads, cfg.index_head_dim
            kq, kk, kw = jax.random.split(next(keys), 3)    # ONE of the
            #   run's keys: a model draws its other weights as it did
            add("wi_q", (ql, hi, di), ql, (None, "heads", "kv"), n_idx, kq)
            add("wi_k", (d, di), d, ("embed", None), n_idx, kk)
            add("wi_w", (d, hi), d, ("embed", "heads"), n_idx, kw)
            p["ik_norm"] = jnp.ones((n_idx, di), pt)
            p["ik_norm_b"] = jnp.zeros((n_idx, di), pt)
            ax["ik_norm"] = ax["ik_norm_b"] = ("layers", None)
        if n_win or cfg.head_gate:
            # a window layer's latent weights, over the window layers alone
            # at their own sizes, and the gates a head: ONE of the run's
            # keys, so a model without either draws its weights as it did
            ks = iter(jax.random.split(next(keys), 8))
            if n_win:
                _init_latent(add, p, ax, cfg.latent_of("window"), n_win, ks,
                             _WIN)
            for n, suffix, kind in ((La - n_win, "", "full"),
                                    (n_win, _WIN, "window")):
                if cfg.head_gate and n:
                    add("wg" + suffix, (d, cfg.latent_of(kind).n_heads), d,
                        ("embed", "heads"), n, next(ks))
    elif La and cfg.attention == "mha":
        add("wq", (d, h, hd), d, ("embed", "heads", "kv"), La)
        for kind in ("full", "window") if cfg.split_kv else ("full",):
            kn, vn = kv_weight_names(cfg, kind)
            n, hk = kind_layers(cfg, run, stack_kinds(cfg, kn)), \
                cfg.kv_heads_of(kind)
            if n:
                add(kn, (d, hk, cfg.key_dim), d, ("embed", "heads", "kv"), n)
                add(vn, (d, hk, vd), d, ("embed", "heads", "kv"), n)
        add("wo", (cfg.out_heads, vd, d), cfg.out_heads * vd,
            ("heads", "kv", "embed"), La)
        if cfg.diff_attn or cfg.attn_bias:      # ONE of the run's keys
            _init_attn_own(p, ax, cfg, run, La, next(keys))
        if cfg.attn_gate:
            add("wg", (d, h, vd), d, ("embed", "heads", "kv"), La)
        n_sink = kind_layers(cfg, run, cfg.sink_kinds)
        if n_sink:      # a logit a query head: zero weighs as a score of 0
            p["sink"] = jnp.zeros((n_sink, h), pt)
            ax["sink"] = ("layers", "heads")
        if cfg.qk_norm:
            p["q_norm"], p["k_norm"] = jnp.ones((La, hd), pt), \
                jnp.ones((La, hd), pt)
            ax["q_norm"] = ax["k_norm"] = ("layers", None)
        n_eva = kind_layers(cfg, run, ("eva",))
        if n_eva:       # a chunk's pooling: a direction and an offset a head
            hk = cfg.kv_heads
            add("adaptive_phi", (hk, hd), hd, ("heads", "kv"), n_eva)
            add("adaptive_mu_k", (hk, hd), hd, ("heads", "kv"), n_eva)
    elif La:
        raise ValueError(f"attention={cfg.attention!r}: expected 'mha' or "
                         f"'mla'")
    if cfg.sandwich_norm:
        p["post_attn_norm"], p["post_mlp_norm"] = jnp.ones((L, d), pt), \
            jnp.ones((L, d), pt)
        ax["post_attn_norm"] = ax["post_mlp_norm"] = ("layers", "embed")
    gated = cfg.activation == "swiglu"
    if cfg.shortcut_moe:    # the routed branch BESIDE the dense feed-forwards
        _init_shortcut(add, p, ax, cfg, L, next(keys))
    if cfg.n_experts and run == "layers" and not cfg.shortcut_moe:
        # the router scores ALL experts; the stacks hold this chip's
        E, held, f = cfg.n_experts, cfg.n_experts_held, cfg.expert_ff_dim
        add("router", (d, E), d, ("embed", "expert"))
        add("w_in", (held, d, f), d, ("expert", "embed", "mlp"))
        add("w_out", (held, f, d), f, ("expert", "mlp", "embed"))
        if gated:
            add("w_gate", (held, d, f), d, ("expert", "embed", "mlp"))
        if cfg.router == "sigmoid":
            # moves the choice of experts, never their weights; float32
            # like the scores it is added to
            p["router_bias"] = jnp.zeros((L, E), pt)
            ax["router_bias"] = ("layers", "expert")
        if cfg.n_shared_experts:
            fs = cfg.n_shared_experts * f
            add("ws_in", (d, fs), d, ("embed", "mlp"))
            add("ws_out", (fs, d), fs, ("mlp", "embed"))
            if gated:
                add("ws_gate", (d, fs), d, ("embed", "mlp"))
    else:
        add("w_in", (d, ff), d, ("embed", "mlp"))
        add("w_out", (ff, d), ff, ("mlp", "embed"))
        if gated:
            add("w_gate", (d, ff), d, ("embed", "mlp"))
    if cfg.norm == "layernorm":
        p["attn_norm_b"] = jnp.zeros((L, d), pt)
        p["mlp_norm_b"] = jnp.zeros((L, d), pt)
        ax["attn_norm_b"] = ax["mlp_norm_b"] = ("layers", "embed")
    return p, ax


def init_params(key: jax.Array, cfg: TransformerConfig
                ) -> Tuple[Params, Params]:
    """Returns (params, params_axes): matching pytrees of weights and
    logical-axis tuples.  Each run of the layer pattern is one stacked
    tree with a leading "layers" axis (pipeline-shardable)."""
    d, pt = cfg.d_model, cfg.param_dtype
    check_kinds(cfg)
    keys = iter(jax.random.split(key, 16))
    params: Params = {
        "embed": {"tok": jax.random.normal(next(keys), (cfg.vocab_size, d),
                                           pt) * 0.02},
        "final_norm": _unit_scale(cfg, (d,)),
    }
    axes: Params = {"embed": {"tok": ("vocab", "embed")},
                    "final_norm": ("embed",)}
    # the main run draws from the model's own keys (a model of one run is
    # initialised as it always was), a leading run from keys of its own
    for i, (run, n) in enumerate(cfg.layer_runs):
        run_keys = keys if run == "layers" else iter(
            jax.random.split(jax.random.fold_in(key, i + 1), 16))
        params[run], axes[run] = _init_run(run_keys, cfg, run, n)
    if cfg.norm == "layernorm":
        params["final_norm_b"] = jnp.zeros((d,), pt)
        axes["final_norm_b"] = ("embed",)
    if cfg.pos_emb == "learned":
        params["embed"]["pos"] = jax.random.normal(
            next(keys), (cfg.max_seq_len, d), pt) * 0.01
        axes["embed"]["pos"] = (None, "embed")
    if cfg.pred_heads > 1 and cfg.tie_embeddings:
        raise ValueError("several prediction heads need an unembedding of "
                         "their own (tie_embeddings=False)")
    if not cfg.tie_embeddings:
        params["lm_head"] = jax.random.normal(
            next(keys), (d, cfg.logit_size), pt) / math.sqrt(d)
        axes["lm_head"] = ("embed", "vocab")
    return params, axes


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def remat_policy(remat):
    """Resolve a config's ``remat`` field to a jax.checkpoint policy, or
    None when remat is off.  Shared by every model family (transformer,
    ViT) so the accepted values can't diverge.

    Full remat (``True``) recomputes what is cheap and keeps what is dear:
    beside the layer's input it saves the flash kernel's output and row
    statistics (`flash_attention.FLASH_OUT`, `FLASH_LSE`: one ``[b, s, d]``
    activation and ``[b, head blocks, 8, s]`` float32 a layer), which is
    all the backward kernels want of the forward one, so the kernel runs
    once a layer (an eighth of the MXU's peak: 65 of 843 ms of
    gpt2-medium's step, PERF.md PR 44).  ``"dots"`` saves the two beside
    its matmul outputs.  A layer whose attention is not the kernel has no
    such name in it, and the policy saves what it saved without them."""
    if not remat:
        return None
    flash = jax.checkpoint_policies.save_only_these_names(
        FLASH_OUT, FLASH_LSE)
    if remat == "dots":     # a kernel call is no dot: saved by its names
        return jax.checkpoint_policies.save_from_both_policies(
            jax.checkpoint_policies.dots_with_no_batch_dims_saveable, flash)
    if remat is True:
        return flash
    # an unrecognized string must not silently mean full remat
    raise ValueError(f"remat={remat!r}: expected False, True, or 'dots'")


def norm_eps(cfg: TransformerConfig) -> float:
    """The epsilon the configuration states, else its norm's own."""
    if cfg.norm_eps is not None:
        return cfg.norm_eps
    return 1e-6 if cfg.norm == "rmsnorm" else 1e-5


# Every part of the model runs under a `jax.named_scope` of its name (one
# of `util.device_profile.MODEL_PARTS`; the innermost counts): the name is
# in every compiled instruction's metadata, which is how a profiler trace's
# device ops are placed in the model.  Metadata only: no instruction changes.

@jax.named_scope("norm")
def _norm(cfg, x, scale, bias):
    if cfg.norm_unit_offset:    # the weight is the scale's distance from 1
        if cfg.norm != "rmsnorm":
            raise ValueError("norm_unit_offset is an RMSNorm's")
        scale = 1.0 + scale.astype(jnp.float32)
    y = rmsnorm(x, scale, norm_eps(cfg)) if cfg.norm == "rmsnorm" \
        else layernorm(x, scale, bias, norm_eps(cfg))
    # a float32 stream is normed for matmuls in the compute type
    return y.astype(cfg.dtype) if cfg.fp32_residual else y


_EXPERT_STACKS = ("w_in", "w_gate", "w_out")
#: ... of a shortcut-connected model, whose layers hold a dense feed-forward
#: under those names too; and what else its routing sublayers alone hold
_SHORTCUT_STACKS = ("we_in", "we_gate", "we_out")
_ROUTING_KEYS = _SHORTCUT_STACKS + ("router", "router_bias")
#: the routers whose expert layer drops no token (`ops.moe.routed_ffn`)
NO_DROP_ROUTERS = ("sigmoid", "softmax_bias")


def scan_layer_runs(cfg: TransformerConfig, params: Params, carry, body,
                    whole_expert_stacks: bool = False, span=None):
    """THE layer loop: ``body(carry, lp, kind) -> carry`` over every layer
    of the declared pattern, one `lax.scan` per segment of identical layers
    (`TransformerConfig.layer_segments`: a run, cut where the kind
    changes), the carry handed from segment to segment.  `_trunk` and
    `generate._scan_cached` both loop through here.

    A segment that is a whole run scans over the run's stacked tree.  One
    that is PART of a run (the kind repeats inside the run) scans over
    layer indices into its stacks (`_scan_part`).

    ``whole_expert_stacks`` (the cached programs): a run's routed-expert
    weights are not scanned over.  The grouped matmul is a kernel call, and
    a layer's ``[E, d, f]`` slice of the stack would be COPIED out for it.
    The body gets ``(stack [L, E, d, f], layer)`` instead and
    `ops.moe.routed_ffn` hands the kernel the whole stack and the layer.

    ``span`` (first layer, one past the last, of the model's; None: all)
    runs the segments in it alone; its ends are ends of segments."""
    run_len = dict(cfg.layer_runs)
    at = 0      # the segment's first layer among the model's
    for run, first, n, kind in cfg.layer_segments:
        at += n
        if span is not None and not span[0] <= at - n < at <= span[1]:
            if span[0] < at and at - n < span[1]:
                raise ValueError(f"span {span} cuts a segment of layers")
            continue
        tree = params[run]
        whole = {k: tree[k] for k in cfg.expert_stacks
                 if whole_expert_stacks and cfg.reports_load
                 and "router" in tree and k in tree}
        xs = {k: v for k, v in tree.items() if k not in whole}

        def layer(c, lp, i):
            return body(c, dict(lp, **{k: (v, i) for k, v in whole.items()}),
                        kind)

        if cfg.shortcut_moe:    # a scan step a PAIR of sublayers
            carry = _scan_pairs(n, xs, layer, carry)
        elif n == run_len[run]:
            carry, _ = jax.lax.scan(
                lambda c, x: (layer(c, *x), None), carry,
                (xs, jnp.arange(n)))
        else:
            # (kept to the lines the loop above it stands on: a Pallas
            # kernel's compile-cache key holds its call stack's line
            # numbers, and the train step's runs through here; PERF.md,
            # PR 30)
            carry = _scan_part(cfg, run, first, n, kind, xs, layer, carry)
    return carry


@jax.named_scope("projections")
def _qkv(cfg: TransformerConfig, y: jnp.ndarray, lp: Params, rotate,
         kind: str = "full"):
    """Normed input [b, s, d] -> (q [b, s, h, hd], k [b, s, hk, hd], v [b,
    s, hk, vd]) of an MHA/GQA block of kind ``kind``: the three
    projections (the value's scaled where the model scales it; each with its
    bias where the model has them), the per-head RMS norms where the model
    has them, then ``rotate`` over a head's first `rope_dim` dims (None:
    this layer turns nothing).  A ``"cross"`` layer has queries alone (k and
    v None: it attends a full layer's rows), and a differential model's
    queries and keys come as PAIRS (`_pair_queries`, `key_dim`)."""
    dt = cfg.dtype
    kn, vn = kv_weight_names(cfg, kind)
    y = mla.times(y, cfg.attn_in_scale)
    q = jnp.einsum("bsd,dhk->bshk", y, lp["wq"].astype(dt))
    if cfg.attn_bias:
        q = q + lp["bq"].astype(dt)
    if kind == "cross":
        return _pair_queries(cfg, q, rotate), None, None
    k = mla.times(jnp.einsum("bsd,dhk->bshk", y, lp[kn].astype(dt)),
                cfg.key_scale)
    v = jnp.einsum("bsd,dhk->bshk", y, lp[vn].astype(dt))
    if cfg.attn_bias:
        k, v = k + lp["bk"].astype(dt), v + lp["bv"].astype(dt)
    if cfg.value_scale != 1.0:
        v = (v.astype(jnp.float32) * cfg.value_scale).astype(dt)
    if cfg.qk_norm:
        q = rmsnorm(q, lp["q_norm"], norm_eps(cfg))
        k = rmsnorm(k, lp["k_norm"], norm_eps(cfg))
    if cfg.diff_attn:
        return _pair_queries(cfg, q, rotate), _pair_keys(cfg, k, rotate), v
    if rotate is not None:
        q, k = _rotate_heads(cfg, rotate, q), _rotate_heads(cfg, rotate, k)
    return q, k, v


def _rotate_heads(cfg: TransformerConfig, rotate, t: jnp.ndarray):
    """``rotate`` over the first `rope_dim` dims of each head of ``t``
    [..., heads, head_dim]; the rest of a head carries no position."""
    if cfg.rope_dim == t.shape[-1]:
        return rotate(t)
    return jnp.concatenate([rotate(t[..., :cfg.rope_dim]),
                            t[..., cfg.rope_dim:]], axis=-1)


def rope_tables(cfg: TransformerConfig, make) -> Dict[str, Any]:
    """``{kind: make(base)}`` over the attention kinds the model rotates,
    each at its kind's base (`TransformerConfig.rope_base_of`); kinds of
    one base share one table."""
    if cfg.pos_emb != "rope":
        return {}
    by_base: Dict[float, Any] = {}
    out = {}
    for kind in ATTENTION_KINDS:
        if kind in cfg.kinds and cfg.rotates(kind):
            base = cfg.rope_base_of(kind)
            if base not in by_base:
                by_base[base] = make(base)
            out[kind] = by_base[base]
    return out


@jax.named_scope("projections")
def _attn_out(cfg: TransformerConfig, y: jnp.ndarray, attn: jnp.ndarray,
              lp: Params, depth=None) -> jnp.ndarray:
    """Heads' output [b, s, h, vd] -> the block's [b, s, d]: gated by
    ``sigmoid(y W_g)`` where the model gates, a pair's two maps subtracted
    under their norm where it is differential (``depth``: the layer's index,
    `diff_pairs`), then the output projection."""
    dt = cfg.dtype
    if cfg.attn_gate:
        gate = jnp.einsum("bsd,dhk->bshk", y, lp["wg"].astype(dt))
        attn = attn * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(dt)
    if cfg.diff_attn:
        attn = diff_pairs(cfg, attn, lp, depth)
    out = jnp.einsum("bshk,hkd->bsd", attn, lp["wo"].astype(dt))
    if cfg.attn_bias:
        out = out + lp["bo"].astype(dt)
    return mla.times(out, cfg.attn_out_scale)


def _post(cfg: TransformerConfig, delta: jnp.ndarray, lp: Params,
          name: str) -> jnp.ndarray:
    """A sandwich model's norm on what a block adds to the residual."""
    if not cfg.sandwich_norm:
        return delta
    return _norm(cfg, delta, lp[name], None)


def _layer(cfg: TransformerConfig, x: jnp.ndarray, lp: Params,
           angles, kind: str = "full", sel=None):
    """One block whose operator is ``kind``'s -> (x, router_aux_loss, sel);
    ``angles`` the (cos, sin) of each kind that rotates (`rope_tables`).
    ``sel`` [b, s, s] bool (None: the model has no indexer) is the
    selection the layer loop carries: an ``"index"`` layer makes a new one
    and attends it, a ``"shared"`` layer attends the one it is handed."""
    cos, sin = angles.get(kind, (None, None))
    if kind in _STATE_LAYERS:
        return _state_layer(cfg, x, lp, kind) + (sel,)
    if kind in _MEMORY_LAYERS:
        return _memory_layer(cfg, x, lp, kind, sel)
    norm = functools.partial(_norm, cfg)
    if cfg.norm_remat:
        norm = jax.checkpoint(
            norm, policy=jax.checkpoint_policies.nothing_saveable)

    y = norm(x, lp["attn_norm"], lp.get("attn_norm_b"))
    if cfg.attention == "mla":
        # nothing is cached here: the plain form, every head's keys and
        # values built from the latents (a window layer's at its own sizes,
        # from its own stacks, under a window mask)
        rotate = functools.partial(apply_rotary, cos=cos, sin=sin) \
            if kind in angles else mla.no_turn
        ck, lw = cfg.latent_of(kind), latent_weights(cfg, lp, kind)
        with latent_scope(cfg, kind):
            q_nope, q_rope, c_q = latent_queries(ck, y, lw, rotate, kind)
            latent = latent_rows(ck, y, lw, rotate, kind)
            if kind == "index":
                s = y.shape[1]
                q_i, k_i, w = index_inputs(cfg, y, c_q, lp, rotate)
                sel = sparse_index.selection_mask(
                    q_i, w, jnp.swapaxes(k_i, 1, 2),
                    jnp.tril(jnp.ones((s, s), bool))[None], cfg.index_topk)
            delta = mla.attend_plain(
                q_nope, q_rope, latent, lw["wkv_b"], lw["wo"],
                causal=cfg.causal, impl=cfg.attention_impl,
                selection=sel if kind in SPARSE_KINDS else None,
                window=cfg.sliding_window if kind == "window" else None,
                gate=head_gate(cfg, y, lw))
        x = x + _post(cfg, delta, lp, "post_attn_norm")
    else:
        q, k, v = _qkv(cfg, y, lp, functools.partial(
            apply_rotary, cos=cos, sin=sin) if kind in angles else None,
            kind)
        if cfg.hands_down:      # a "cross" layer's rows are handed to it
            delta, sel = _handing_attention(cfg, y, q, k, v, lp, kind, sel)
        elif kind == "eva":     # the plain form: dense over the sequence
            delta = _attn_out(cfg, y, eva_attention(
                q, k, v, lp["adaptive_phi"], lp["adaptive_mu_k"],
                window=cfg.sliding_window, chunk=cfg.summary_chunk), lp)
        else:
            delta = _attn_out(cfg, y, multi_head_attention(
                q, k, v, causal=cfg.causal, impl=cfg.attention_impl,
                window=cfg.sliding_window if kind == "window" else None,
                sink=lp["sink"] if kind in cfg.sink_kinds else None), lp)
        if kind in SSM_KINDS:   # the mixer off the same norm: the sum
            delta = delta + ssm_operator(cfg, y, lp)[0]
        x = x + _post(cfg, delta, lp, "post_attn_norm")

    y = norm(x, lp["mlp_norm"], lp.get("mlp_norm_b"))
    z, aux, _ = _ffn(cfg, y, lp)
    if cfg.shortcut_moe:    # the routed branch leaves here or rejoins here
        z, _, sel = shortcut(cfg, y, z, lp, sel)
        sel = hand_on(sel)
    return x + _post(cfg, z, lp, "post_mlp_norm"), aux, sel


@jax.named_scope("ffn")
def _glu(cfg: TransformerConfig, y, w_in, w_gate, w_out) -> jnp.ndarray:
    """A dense feed-forward: SwiGLU where the model gates, else GELU."""
    dt = cfg.dtype
    up = jnp.einsum("bsd,df->bsf", y, w_in.astype(dt))
    if cfg.activation == "swiglu":
        gate = mla.times(jnp.einsum("bsd,df->bsf", y, w_gate.astype(dt)),
                       cfg.ffn_gate_scale)
        z = jax.nn.silu(gate) * up
    else:
        z = jax.nn.gelu(up)
    return mla.times(jnp.einsum("bsf,fd->bsd", z, w_out.astype(dt)),
                   cfg.ffn_out_scale)


def no_load(cfg: TransformerConfig) -> Tuple[int, ...]:
    """What a layer that routes nothing hands the loop for `ops.moe.Load`."""
    return (0,) * cfg.load_counts


def _ffn(cfg: TransformerConfig, y: jnp.ndarray, lp: Params,
         valid: Optional[jnp.ndarray] = None):
    """Post-attention FFN on a normed input — ONE implementation shared
    by training/prefill (`_layer`) and KV-cache decode
    (`models/generate.py`), so the architectures can't desynchronize.
    Which FFN a layer has follows from its run's tree (a router or none),
    which expert layer from the model's router kind (a shortcut-connected
    model's every sublayer has the dense one HERE and its routed branch
    beside it, `shortcut`).  ``valid`` [b, s]
    marks the rows that count (a decode batch's live slots); only the
    no-drop expert layer, whose cost follows the rows routed, looks at it.
    → (residual delta, router aux loss, (experts touched, largest expert
    load, pairs that landed on an expert held here) of this layer, zeros
    where it routes nothing)."""
    aux = jnp.zeros((), jnp.float32)
    if "router" not in lp or cfg.shortcut_moe:
        return (_glu(cfg, y, lp["w_in"], lp.get("w_gate"), lp["w_out"]),
                aux, no_load(cfg))
    if cfg.router == "softmax":
        from ..ops.moe import moe_ffn
        z, aux = moe_ffn(
            y, lp["router"], lp["w_in"], lp["w_out"], lp.get("w_gate"),
            top_k=cfg.expert_top_k, capacity_factor=cfg.capacity_factor)
        return z, aux, no_load(cfg)
    z, load = routed_branch(cfg, y, lp, valid)
    if cfg.n_shared_experts:
        z = z + _glu(cfg, y, lp["ws_in"], lp.get("ws_gate"), lp["ws_out"])
    return z, aux, load


def _trunk(params: Params, tokens: jnp.ndarray, cfg: TransformerConfig
           ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Everything up to (and including) the final norm:
    tokens [b, s] → (hidden [b, s, d] in cfg.dtype, mean router aux)."""
    check_kinds(cfg)
    x = _embed(params, tokens, cfg)
    angles = rope_tables(cfg, lambda base: rotary_angles(
        tokens.shape[1], cfg.rope_dim, base))

    policy = remat_policy(cfg.remat)

    def body(carry, lp, kind="full"):
        layer = functools.partial(_layer, cfg, kind=kind)
        if policy is not None:
            layer = jax.checkpoint(layer, static_argnums=(), policy=policy)
        h, aux, sel = carry
        h, aux_l, sel = layer(h, lp, angles, sel=sel)
        return h, aux + aux_l, sel

    if cfg.pp_stages > 1:
        from ..parallel.pipeline import (microbatch, pipeline_apply,
                                         unmicrobatch)
        if cfg.n_layers % cfg.pp_stages:
            raise ValueError(f"{cfg.n_layers} layers not divisible by "
                             f"{cfg.pp_stages} pipeline stages")
        if len(cfg.layer_segments) > 1 or cfg.index_topk:
            raise NotImplementedError(
                "a pipeline over a layer pattern of more than one run "
                "or kind (leading dense layers, mixed layer_kinds) is not "
                "supported: stages are equal slabs of ONE stacked tree")
        n_micro = cfg.pp_microbatches or cfg.pp_stages

        def stage_fn(slab, state):
            out, _ = jax.lax.scan(
                lambda c, lp: (body(c + (None,), lp)[:2], None), state, slab)
            return out

        x_mb = (microbatch(x, n_micro),
                jnp.zeros((n_micro,), jnp.float32))
        h_mb, aux_mb = pipeline_apply(
            stage_fn, params["layers"], x_mb,
            n_stages=cfg.pp_stages, n_micro=n_micro)
        x = unmicrobatch(h_mb)
        aux = aux_mb.sum() / (n_micro * cfg.n_layers)
    else:
        b, s = tokens.shape     # the selection an indexing layer hands on
        sel = jnp.zeros((b, s, s), bool) if cfg.index_topk else None
        if cfg.hands_down:      # ... or what a memory model's layers hand on
            sel = handed(cfg, b, s, rows=True)
        x, aux, _ = scan_layer_runs(
            cfg, params, (x, jnp.zeros((), jnp.float32), sel), body)
        aux = aux / cfg.n_layers
    x = _norm(cfg, x, params["final_norm"], params.get("final_norm_b"))
    return x, aux


@jax.named_scope("embed")
def _embed(params: Params, tokens: jnp.ndarray, cfg: TransformerConfig
           ) -> jnp.ndarray:
    """tokens [b, s] -> the first layer's input [b, s, d] in cfg.dtype: the
    token table's rows, scaled, plus the learned positions where the model
    has them."""
    b, s = tokens.shape
    dt = cfg.stream_dtype
    if cfg.embed_impl == "one_hot":
        # gather's backward is a scatter-add into [vocab, d] — serialized
        # and slow on TPU; the one-hot formulation turns fwd AND bwd into
        # MXU matmuls.  Chunked over tokens so the one-hot buffer peaks
        # at [chunk, vocab] (~100 MB bf16 at vocab 50k) instead of
        # [b*s, vocab] (~820 MB at b8/s1024) — XLA may fuse it away, but
        # the bound must not depend on that.
        emb = params["embed"]["tok"].astype(dt)
        flat = tokens.reshape(-1)
        chunk = 1024
        if flat.size <= chunk:
            x = jax.nn.one_hot(flat, cfg.vocab_size, dtype=dt) @ emb
        else:
            pad = (-flat.size) % chunk
            chunks = jnp.pad(flat, (0, pad)).reshape(-1, chunk)
            x = jax.lax.map(
                lambda t: jax.nn.one_hot(t, cfg.vocab_size, dtype=dt)
                @ emb, chunks).reshape(-1, cfg.d_model)[:flat.size]
        x = x.reshape(b, s, cfg.d_model)
    elif cfg.embed_impl == "gather":
        x = params["embed"]["tok"][tokens].astype(dt)
    else:  # a typo must not silently mean the gather path (cf. remat_policy)
        raise ValueError(f"embed_impl={cfg.embed_impl!r}: expected "
                         f"'gather' or 'one_hot'")
    x = _scale_embedding(cfg, x)
    if cfg.pos_emb == "learned":
        x = x + params["embed"]["pos"][:s].astype(dt)
    return x


def _scale_embedding(cfg: TransformerConfig, x: jnp.ndarray) -> jnp.ndarray:
    """The embedding multiplier of a model that states one."""
    return mla.times(x, cfg.embed_scale)


@jax.named_scope("head")
def _unembed(params: Params, cfg: TransformerConfig) -> jnp.ndarray:
    w = (params["embed"]["tok"].T if cfg.tie_embeddings
         else params["lm_head"])
    return w.astype(cfg.dtype)


def forward_with_aux(params: Params, tokens: jnp.ndarray,
                     cfg: TransformerConfig
                     ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """tokens [batch, seq] int32 → (logits [batch, seq, vocab] fp32,
    mean router aux loss).  With ``cfg.pp_stages > 1`` the layer stack runs
    as a GPipe pipeline over the ambient mesh's ``pp`` axis
    (parallel/pipeline.py); otherwise a plain `lax.scan`."""
    x, aux = _trunk(params, tokens, cfg)
    # fp32 MXU accumulation straight out of the dot — rounding the logits
    # through bf16 first would cost ~3 decimal digits on a 50k-way softmax
    with jax.named_scope("head"):
        logits = mla.times(jnp.einsum("bsd,dv->bsv", x, _unembed(params, cfg),
                                    preferred_element_type=jnp.float32),
                         cfg.logit_scale)
    return logits, aux


def forward(params: Params, tokens: jnp.ndarray,
            cfg: TransformerConfig) -> jnp.ndarray:
    """tokens [batch, seq] int32 → logits [batch, seq, vocab] fp32."""
    return forward_with_aux(params, tokens, cfg)[0]


def lm_loss(params: Params, batch: Dict[str, jnp.ndarray],
            cfg: TransformerConfig) -> jnp.ndarray:
    """Next-token cross entropy.  ``batch`` has "tokens" [b, s]; loss is on
    positions 0..s-2 predicting 1..s-1.

    With ``cfg.loss_chunk`` set (and dividing s), the unembed + softmax
    runs chunk-by-chunk under `jax.checkpoint`, so only one
    [b, chunk, vocab] logits block exists at a time (forward AND
    backward) instead of the full [b, s, vocab] fp32 tensor.
    """
    import optax

    # run the model on the FULL sequence and shift the logits: keeps the
    # model's seq length divisible by sequence-parallel mesh axes (sp)
    tokens = batch["tokens"]
    b, s = tokens.shape
    # the sigmoid router's correction bias is a constant here: no
    # auxiliary loss (its update is a training procedure of its own)
    aux_weight = cfg.router_aux_weight \
        if cfg.n_experts and cfg.router == "softmax" else 0.0
    mask = batch.get("mask")

    if cfg.loss_chunk and s % cfg.loss_chunk:
        # falling back silently would re-materialize the full
        # [b, s, vocab] logits — the OOM cliff loss_chunk exists to avoid
        raise ValueError(f"seq length {s} is not divisible by "
                         f"loss_chunk={cfg.loss_chunk}")
    if cfg.pred_heads > 1:
        return _multi_head_loss(params, tokens, mask, cfg)
    if cfg.loss_chunk:
        x, aux = _trunk(params, tokens, cfg)
        with jax.named_scope("head"):
            w_out = _unembed(params, cfg)
            # target for the LAST position is a dummy masked to weight 0
            targets = jnp.concatenate(
                [tokens[:, 1:], jnp.zeros((b, 1), tokens.dtype)], axis=1)
            valid = jnp.concatenate(
                [jnp.ones((b, s - 1), jnp.float32),
                 jnp.zeros((b, 1), jnp.float32)], axis=1)
            if mask is not None:
                shifted = jnp.concatenate(
                    [mask[:, 1:], jnp.zeros((b, 1), mask.dtype)], axis=1)
                valid = valid * shifted.astype(jnp.float32)
            n = s // cfg.loss_chunk
            xc = jnp.swapaxes(x.reshape(b, n, cfg.loss_chunk, -1), 0, 1)
            tc = jnp.swapaxes(targets.reshape(b, n, cfg.loss_chunk), 0, 1)
            vc = jnp.swapaxes(valid.reshape(b, n, cfg.loss_chunk), 0, 1)

            def chunk_sum(xi, ti, vi):
                logits = mla.times(jnp.einsum(
                    "bcd,dv->bcv", xi, w_out,
                    preferred_element_type=jnp.float32), cfg.logit_scale)
                ls = optax.softmax_cross_entropy_with_integer_labels(
                    logits, ti)
                return (ls * vi).sum()

            def body(acc, inp):
                xi, ti, vi = inp
                return acc + jax.checkpoint(chunk_sum)(xi, ti, vi), None

            total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32),
                                    (xc, tc, vc))
            return total / jnp.maximum(valid.sum(), 1.0) + aux_weight * aux

    logits, aux = forward_with_aux(params, tokens, cfg)
    with jax.named_scope("head"):
        logits = logits[:, :-1]
        losses = optax.softmax_cross_entropy_with_integer_labels(
            logits, tokens[:, 1:])
        aux_term = aux_weight * aux
        if mask is not None:
            m = mask[:, 1:].astype(jnp.float32)
            return (losses * m).sum() / jnp.maximum(m.sum(), 1.0) + aux_term
        return losses.mean() + aux_term


def make_train_step(cfg: TransformerConfig, optimizer, accum_steps: int = 1):
    """(params, opt_state, batch) → (params, opt_state, metrics); pure, jit
    it under any mesh/sharding.

    ``accum_steps > 1`` runs gradient accumulation INSIDE the compiled
    step: the batch is split into ``accum_steps`` microbatches scanned
    with a summed f32 grad carry, and the optimizer applies once.  Two
    uses: (a) effective batches beyond HBM (activation memory scales
    with the microbatch), and (b) on memory-bound chips the Adam-moment
    read/write traffic amortizes over ``accum_steps`` × more tokens —
    the operating point `gpt2-medium.train-1024` runs (32 sequences
    a step in micro-batches of 8; PERF.md section 4 has its memory and
    section 5 its step time)."""

    def grad_fn(params, batch):
        return jax.value_and_grad(
            functools.partial(lm_loss, cfg=cfg))(params, batch)

    def step(params, opt_state, batch):
        # (parameters and state go back laid out as they came: `_stepped`,
        # at the file's end for the sake of every line number below)
        if accum_steps > 1:
            full = batch["tokens"].shape[0]
            if full % accum_steps:
                raise ValueError(
                    f"batch {full} not divisible by "
                    f"accum_steps {accum_steps}")
            micro = full // accum_steps
            # split EVERY batch leaf (tokens, mask, ...) on the batch
            # axis so the microbatch loss sees the same keys the flat
            # path does
            mbatch = jax.tree_util.tree_map(
                lambda v: v.reshape((accum_steps, micro) + v.shape[1:]),
                batch)

            def micro_step(carry, mb):
                gsum, lsum, csum = carry
                loss, grads = grad_fn(params, mb)
                # weight by this microbatch's valid-token count so the
                # combined gradient equals the FULL-batch step even when
                # a padding mask is uneven across microbatches (lm_loss
                # normalizes per call by its own mask[:, 1:].sum();
                # equal 1/accum weighting would over-weight nearly-empty
                # microbatches)
                if "mask" in mb:
                    count = mb["mask"][:, 1:].astype(jnp.float32).sum()
                else:
                    count = jnp.float32(micro
                                        * (mb["tokens"].shape[1] - 1))
                with jax.named_scope("optimizer"):
                    gsum = jax.tree_util.tree_map(
                        lambda a, g: a + g.astype(jnp.float32) * count,
                        gsum, grads)
                    return (gsum, lsum + loss * count, csum + count), None

            with jax.named_scope("optimizer"):
                zeros = jax.tree_util.tree_map(
                    lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (gsum, lsum, csum), _ = jax.lax.scan(
                micro_step, (zeros, jnp.float32(0.0), jnp.float32(0.0)),
                mbatch)
            with jax.named_scope("optimizer"):
                csum = jnp.maximum(csum, 1.0)
                # back to the dtype grad_fn itself produces (param dtype)
                # so optimizer state dtypes — and therefore buffer
                # donation — match the accum_steps=1 path
                grads = jax.tree_util.tree_map(
                    lambda g, p: (g / csum).astype(p.dtype), gsum, params)
                loss = lsum / csum
        else:
            loss, grads = grad_fn(params, batch)
        with jax.named_scope("optimizer"):
            updates, state = optimizer.update(grads, opt_state, params)
            params, opt_state = _stepped(params, updates, state, opt_state)
            gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                                 for g in jax.tree_util.tree_leaves(grads)))
        return params, opt_state, {"loss": loss, "grad_norm": gnorm}

    # in the compile ledger by its name alone: the op map of every
    # executable JAX loads for a jit of ``step``, and no timing shim (a
    # sampled ``block_until_ready`` would empty a loop that keeps steps in
    # flight).  The function itself is returned and gets NO attribute:
    # `jax.jit` copies a function's ``__dict__`` onto what it returns, so
    # a ``step.lower`` here would stand in for the caller's jit's own and
    # lower the step without its shardings and donation.
    from ..util.device_profile import watch
    watch("train_step", step)
    return step


# ---------------------------------------------------------------------------
# two operators in one run (appended here: see `scan_layer_runs`' note on
# the line numbers above)
# ---------------------------------------------------------------------------

#: a conv layer's weights in a run's tree
_CONV_KEYS = ("conv_in", "conv_w", "conv_out")
#: a KDA layer's (`_init_kda`)
_KDA_KEYS = ("kda_in", "kda_conv", "kda_lo", "kda_fb", "kda_gb", "kda_a_log",
             "kda_dt_bias", "kda_norm", "kda_out")
#: a state-space mixer's (`_init_ssm`)
_SSM_KEYS = ("ssm_in", "ssm_conv", "ssm_conv_b", "ssm_dt_bias", "ssm_a_log",
             "ssm_d", "ssm_norm", "ssm_out")
#: the kinds of layer whose operator is no attention and carries a state
_STATE_LAYERS = ("conv", "kda")
#: a selective scan's weights (`_init_memory`), and a gated memory unit's
_MAMBA_KEYS = ("mamba_in", "mamba_conv", "mamba_conv_b", "mamba_x",
               "mamba_dt", "mamba_dt_b", "mamba_a_log", "mamba_d",
               "mamba_out")
_GMU_KEYS = ("gmu_in", "gmu_out")
#: the kinds of layer around a MEMORY: a selective scan that makes one (and
#: carries a state), a gated unit that reads the last one made
_MEMORY_LAYERS = ("mamba", "gmu")
#: ... and the kinds that hold NO state of their own: a gated memory unit
#: reads the scan output of the last ``"mamba"`` layer before it, a
#: ``"cross"`` layer attends the rows of the last ``"full"`` layer before it
READER_KINDS = ("gmu", "cross")
#: the kinds whose model hands values down the layer loop (`handed`)
HANDING_KINDS = ("mamba",) + READER_KINDS
#: the kinds of layer that attend nothing
_NO_ATTENTION = _STATE_LAYERS + _MEMORY_LAYERS
#: the kinds of layer with a state-space mixer BESIDE their attention: two
#: operators off one norm, a state and rows at once
SSM_KINDS = ("ssm+full",)
#: an attention layer's (MHA/GQA and latent), of whatever attention kind
_ATTN_KEYS = ("wq", "wk", "wv", "wo", "wg", "q_norm", "k_norm", "wq_a",
              "wq_b", "wkv_a", "wkv_b", "kv_norm", "bq", "bk", "bv", "bo",
              "diff_lq1", "diff_lk1", "diff_lq2", "diff_lk2", "diff_norm")
#: a differential layer's four ``lambda`` vectors
_LAMBDA_KEYS = ("diff_lq1", "diff_lk1", "diff_lq2", "diff_lk2")
#: ... of which a layer that reads another layer's rows has none
_ROW_WEIGHTS = ("wk", "wv", "bk", "bv")
_WINDOW_KV = ("wk_win", "wv_win")
_WIN = "_win"
#: a latent layer's weights (`_init_latent`, the gate a head), and a window
#: latent layer's own stacks of them where a model holds them apart
_LATENT_KEYS = ("wq_a", "wq_b", "q_norm", "wq", "wkv_a", "wkv_b", "kv_norm",
                "wo", "wg")
_WINDOW_LATENT = tuple(k + _WIN for k in _LATENT_KEYS)
#: a chunk's pooling, of the layers that attend through summaries
_EVA_KEYS = ("adaptive_phi", "adaptive_mu_k")
#: an indexer's weights, of the indexing layers alone
_INDEX_KEYS = ("wi_q", "wi_k", "wi_w", "ik_norm", "ik_norm_b")
#: the kinds of layer of a latent-attention model with an indexer: one that
#: scores and chooses, one that attends the choice of the last such before it
SPARSE_KINDS = ("index", "shared")
#: the kinds of layer whose operator is attention
ATTENTION_KINDS = ("full", "window", "eva") + SPARSE_KINDS + SSM_KINDS \
    + ("cross",)


def check_kinds(cfg: TransformerConfig) -> None:
    """What an indexer needs of a configuration, refused with a message
    where it lacks it."""
    if set(cfg.kinds) & set(SSM_KINDS) and (
            min(cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state,
                cfg.ssm_groups) < 1 or cfg.ssm_heads % cfg.ssm_groups
            or cfg.ssm_conv_kernel < 2 or len(cfg.ssm_scales) != 5
            or cfg.attention != "mha"):
        raise ValueError(
            f"layer_kinds {cfg.layer_kinds!r}: an 'ssm+full' layer needs "
            f"ssm_heads, ssm_head_dim and ssm_state of at least 1, whole "
            f"groups of heads (ssm_groups divides ssm_heads), an "
            f"ssm_conv_kernel of at least 2, five ssm_scales and MHA/GQA "
            f"attention beside it")
    if "kda" in cfg.kinds and (
            min(cfg.kda_heads, cfg.kda_head_dim, cfg.kda_gate_rank) < 1
            or cfg.kda_conv_kernel < 2):
        raise ValueError(
            f"layer_kinds {cfg.layer_kinds!r}: a 'kda' layer needs kda_heads, "
            f"kda_head_dim and kda_gate_rank of at least 1 and a "
            f"kda_conv_kernel of at least 2")
    _check_memory(cfg)
    _check_routing(cfg)
    sparse = set(cfg.kinds) & set(SPARSE_KINDS)
    if not sparse and not cfg.index_topk:
        return
    if not sparse or set(cfg.kinds) - sparse - {"window"} \
            or cfg.attention != "mla" \
            or min(cfg.index_topk, cfg.index_heads, cfg.index_head_dim) < 1 \
            or cfg.index_head_dim < cfg.qk_rope_head_dim:
        raise ValueError(
            f"layer_kinds {cfg.layer_kinds!r} with index_topk "
            f"{cfg.index_topk}: an indexer is a latent-attention model's "
            f"(attention='mla'), every layer of which is 'index', 'shared' "
            f"or 'window' and which states index_topk, index_heads and "
            f"index_head_dim (at least the rotary part's "
            f"{cfg.qk_rope_head_dim})")
    if next(k for k in cfg.kinds if k in SPARSE_KINDS) != "index":
        where = "the first layer is 'shared'" if cfg.kinds[0] == "shared" \
            else "a 'shared' layer stands behind 'window' layers alone " \
                 "(a window layer makes no choice and hands none on)"
        raise ValueError(
            f"layer_kinds {cfg.layer_kinds!r}: {where}, and no indexing "
            f"layer stands before it whose choice it could attend")


def index_inputs(cfg: TransformerConfig, y: jnp.ndarray, c_q: jnp.ndarray,
                 lp: Params, rotate):
    """An indexing layer's own projections of the normed input ``y`` [b, s,
    d] and the query latent ``c_q`` -> (qI [b, s, heads, dim], kI [b, s,
    dim], w [b, s, heads] float32); the rotary part is the latent
    attention's (the first `rope_dim` dims, the same angles)."""
    rope = cfg.rope_dim
    return (sparse_index.index_queries(c_q, lp["wi_q"], rotate=rotate,
                                       rope=rope),
            sparse_index.index_keys(y, lp["wi_k"], lp["ik_norm"],
                                    lp["ik_norm_b"], rotate=rotate,
                                    rope=rope),
            sparse_index.head_weights(y, lp["wi_w"], cfg.index_head_dim))


def kv_weight_names(cfg: TransformerConfig, kind: str) -> Tuple[str, str]:
    """The key and value projections of an attention layer of ``kind`` in
    its run's tree: a window layer's have stacks of their own where their
    shape is not the full layers' (`TransformerConfig.split_kv`)."""
    return _WINDOW_KV if kind == "window" and cfg.split_kv else ("wk", "wv")


def stack_kinds(cfg: TransformerConfig, key: str
                ) -> Optional[Tuple[str, ...]]:
    """The kinds of layer over which the stack ``key`` of a run's tree
    runs (None: every layer of the run): an operator's weights hold no
    other operator's layers, and a weight whose shape follows the layer's
    kind only that kind's."""
    if key in _CONV_KEYS:
        return ("conv",)
    if key in _KDA_KEYS:
        return ("kda",)
    if key in _SSM_KEYS:
        return SSM_KINDS
    if key == "sink":
        return cfg.sink_kinds
    if key in _WINDOW_KV or key in _WINDOW_LATENT:
        return ("window",)
    if key in _EVA_KEYS:
        return ("eva",)
    if key in _INDEX_KEYS:
        return ("index",)
    if key in _MAMBA_KEYS:
        return ("mamba",)
    if key in _GMU_KEYS:
        return ("gmu",)
    if key in _ATTN_KEYS:
        if cfg.split_kv and key in ("wk", "wv"):
            return ("full",)
        # (a summary, indexing, shared or cross layer is named only by a
        # model that has one; a latent model's window layers have stacks of
        # their own; a cross layer has no rows to project)
        return ("full",) + (() if cfg.window_latent else ("window",)) \
            + tuple(k for k in ATTENTION_KINDS[2:] if k in cfg.kinds
                    and not (k == "cross" and key in _ROW_WEIGHTS))
    return None


def kind_layers(cfg: TransformerConfig, run: str,
                kinds: Optional[Tuple[str, ...]],
                upto: Optional[int] = None) -> int:
    """Layers of the kinds ``kinds`` (None: any) among the first ``upto``
    layers (None: all) of the run ``run`` of `layer_runs`: how long a stack
    over those kinds is in that run's tree, and where a segment's first
    layer stands in it."""
    first = 0
    for name, n in cfg.layer_runs:
        if name == run:
            mine = cfg.kinds[first:first + (n if upto is None else upto)]
            return len(mine) if kinds is None else \
                sum(k in kinds for k in mine)
        first += n
    raise KeyError(run)


def operator_layers(cfg: TransformerConfig, run: str,
                    upto: Optional[int] = None) -> Tuple[int, int]:
    """(attention layers, conv layers) among the first ``upto`` layers
    (None: all) of the run ``run`` of `layer_runs`."""
    return (kind_layers(cfg, run, ATTENTION_KINDS, upto),
            kind_layers(cfg, run, ("conv",), upto))


def _scan_part(cfg: TransformerConfig, run: str, first: int, n: int,
               kind: str, xs: Params, layer, carry):
    """`scan_layer_runs` over the ``n`` layers from ``first`` of a run
    whose layers are not all of one kind: a scan over layer INDICES into
    the run's stacks (a slice of a stack would be a copy of those layers'
    weights on every call).  Each stack is indexed by the count of ITS
    layers before (`stack_kinds`: what every layer has by the layer, an
    operator's weights by that operator's layers, a kind's own by that
    kind's), and a stack that holds none of this kind's is left out."""
    starts = {}         # key -> this segment's first layer in its stack
    for k in xs:
        kinds = stack_kinds(cfg, k)
        if kinds is None or kind in kinds:
            starts[k] = kind_layers(cfg, run, kinds, first)

    def step(c, j):
        lp = {k: jax.lax.dynamic_index_in_dim(xs[k], i + j, 0,
                                              keepdims=False)
              for k, i in starts.items()}
        return layer(c, lp, first + j), None

    carry, _ = jax.lax.scan(step, carry, jnp.arange(n))
    return carry


def _state_layer(cfg: TransformerConfig, x: jnp.ndarray, lp: Params,
                 kind: str) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """`_layer` for a conv or a KDA layer over a whole sequence (no state
    carried in): the gated short convolution, or the gated delta rule in
    its plain form, in attention's place, then the layer's feed-forward
    as every layer has it."""
    from ..ops.short_conv import conv_block
    y = _norm(cfg, x, lp["attn_norm"], lp.get("attn_norm_b"))
    if kind == "kda":
        delta, _, _ = kda_operator(cfg, y, lp)
    else:
        delta, _ = conv_block(y, lp["conv_in"], lp["conv_w"],
                              lp["conv_out"])
    x = x + _post(cfg, delta, lp, "post_attn_norm")
    y = _norm(cfg, x, lp["mlp_norm"], lp.get("mlp_norm_b"))
    z, aux, _ = _ffn(cfg, y, lp)
    return x + _post(cfg, z, lp, "post_mlp_norm"), aux


def _unit_scale(cfg: TransformerConfig, shape) -> jnp.ndarray:
    """A norm's weight that multiplies by one: ones, or, where the norm adds
    the unit itself (``norm_unit_offset``), zeros."""
    return (jnp.zeros if cfg.norm_unit_offset else jnp.ones)(
        shape, cfg.param_dtype)


def _multi_head_loss(params: Params, tokens: jnp.ndarray, mask,
                     cfg: TransformerConfig) -> jnp.ndarray:
    """`lm_loss` of a model with several prediction heads: head ``p``'s
    cross entropy against the token ``1 + p`` positions on, each head's mean
    over the positions that have such a token, the heads' mean."""
    import optax
    if cfg.loss_chunk:
        raise NotImplementedError("a chunked loss over several prediction "
                                  "heads")
    logits, _ = forward_with_aux(params, tokens, cfg)
    s, v = tokens.shape[1], cfg.vocab_size
    with jax.named_scope("head"):
        total = 0.0
        for p in range(cfg.pred_heads):
            losses = optax.softmax_cross_entropy_with_integer_labels(
                logits[:, :s - 1 - p, p * v:(p + 1) * v], tokens[:, 1 + p:])
            if mask is None:
                total += losses.mean()
            else:
                m = mask[:, 1 + p:].astype(jnp.float32)
                total += (losses * m).sum() / jnp.maximum(m.sum(), 1.0)
        return total / cfg.pred_heads


def _init_latent(add, p: Params, ax: Params, cfg: TransformerConfig, n: int,
                 keys, suffix: str = "") -> None:
    """A run's latent-attention weights at ``cfg``'s latent sizes (a kind's
    own: `TransformerConfig.latent_of`), stacked over ``n`` layers under
    their names + ``suffix``, a key of ``keys`` drawn a projection."""
    d, h, pt = cfg.d_model, cfg.n_heads, cfg.param_dtype
    ql, kl = cfg.q_lora_rank, cfg.kv_lora_rank
    nope, rope, vd = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                      cfg.v_head_dim)
    # (what reads a RESCALED latent is drawn as if it read the model's
    # width: the multiplier then leaves a head's query, key and value at the
    # variance every other projection's output has)
    up = (lambda rank: d) if cfg.latent_rescale else (lambda rank: rank)
    if ql:
        add("wq_a" + suffix, (d, ql), d, ("embed", None), n, next(keys))
        add("wq_b" + suffix, (ql, h, nope + rope), up(ql),
            (None, "heads", "kv"), n, next(keys))
        p["q_norm" + suffix] = jnp.ones((n, ql), pt)
        ax["q_norm" + suffix] = ("layers", None)
    else:   # no query latent: the heads' queries from the input
        add("wq" + suffix, (d, h, nope + rope), d, ("embed", "heads", "kv"),
            n, next(keys))
    add("wkv_a" + suffix, (d, kl + rope), d, ("embed", None), n, next(keys))
    add("wkv_b" + suffix, (kl, h, nope + vd), up(kl), (None, "heads", "kv"),
        n, next(keys))
    add("wo" + suffix, (h, vd, d), h * vd, ("heads", "kv", "embed"), n,
        next(keys))
    p["kv_norm" + suffix] = jnp.ones((n, kl), pt)
    ax["kv_norm" + suffix] = ("layers", None)


def latent_weights(cfg: TransformerConfig, lp: Params, kind: str) -> Params:
    """A latent layer's weights under their plain names: a window layer's
    are its run's ``*_win`` stacks where the model holds those apart."""
    if kind != "window" or not cfg.window_latent:
        return lp
    return dict(lp, **{k[:-len(_WIN)]: v for k, v in lp.items()
                       if k.endswith(_WIN)})


def latent_queries(cfg: TransformerConfig, y: jnp.ndarray, lp: Params,
                   rotate, kind: str = "full"):
    """A latent-attention layer's queries of the normed input ``y`` ->
    (q_nope, q_rope turned by ``rotate``, the query latent or None):
    through a query latent, its norm and the model's fixed multiplier on it,
    or, of a model that has none (``q_lora_rank`` 0), projected directly.
    ``cfg`` and ``lp`` are the layer's kind's (`TransformerConfig.latent_of`,
    `latent_weights`)."""
    nope = cfg.qk_nope_head_dim
    if cfg.q_lora_rank:
        return mla.queries(y, lp["wq_a"], lp["q_norm"], lp["wq_b"],
                           nope=nope, eps=norm_eps(cfg), rotate=rotate,
                           scale=cfg.latent_scales(kind)[0])
    return mla.direct_queries(y, lp["wq"], nope=nope, rotate=rotate) + (None,)


def latent_rows(cfg: TransformerConfig, y: jnp.ndarray, lp: Params, rotate,
                kind: str = "full") -> jnp.ndarray:
    """What a latent layer's cache holds of the normed input ``y`` [b, s, d]
    -> [b, s, kv_lora + rope] (`mla.latents`), the latent under the model's
    fixed multiplier; ``cfg`` and ``lp`` the layer's kind's."""
    return mla.latents(y, lp["wkv_a"], lp["kv_norm"],
                       kv_lora=cfg.kv_lora_rank, eps=norm_eps(cfg),
                       rotate=rotate, scale=cfg.latent_scales(kind)[1])


@jax.named_scope("projections")
def head_gate(cfg: TransformerConfig, y: jnp.ndarray, lp: Params):
    """``sigmoid(y W_g)`` [b, s, heads], one value a head, of a model whose
    latent layers gate their heads' output (None: it does not)."""
    if not cfg.head_gate:
        return None
    gate = jnp.einsum("bsd,dh->bsh", y, lp["wg"].astype(y.dtype))
    return jax.nn.sigmoid(gate.astype(jnp.float32)).astype(y.dtype)


def latent_scope(cfg: TransformerConfig, kind: str):
    """The scope ``window_latent`` AROUND all of a window latent layer's
    operator (its parts keep their own names inside it, as ``ssm`` stands
    around a mixer's), nothing for any other layer."""
    return jax.named_scope("window_latent") \
        if kind == "window" and cfg.window_latent \
        else contextlib.nullcontext()


def _init_kda(add, p: Params, ax: Params, cfg: TransformerConfig, n: int,
              key) -> None:
    """A run's KDA weights, stacked over its ``n`` KDA layers: the three
    input projections as one (``kda_in``: q | k | v), their convolutions as
    one (``kda_conv``), the three small projections of the input as one
    (``kda_lo``: the decay's first step | the output gate's | the step size
    a head), the two second steps, the decay a head and its bias a channel,
    the heads' norm, the output projection.  What decides how long a state
    remembers is drawn as published: ``A_log = log U(1, 16)``, ``dt = exp
    U(log 0.001, log 0.1)``, ``dt_bias = dt + log(-expm1(-dt))``."""
    d, h, hd, r = (cfg.d_model, cfg.kda_heads, cfg.kda_head_dim,
                   cfg.kda_gate_rank)
    e, taps, pt = h * hd, cfg.kda_conv_kernel, cfg.param_dtype
    ks = iter(jax.random.split(key, 8))
    add("kda_in", (d, 3 * e), d, ("embed", None), n, next(ks))
    add("kda_conv", (3 * e, taps), taps, (None, None), n, next(ks))
    add("kda_lo", (d, 2 * r + h), d, ("embed", None), n, next(ks))
    add("kda_fb", (r, e), r, (None, None), n, next(ks))
    add("kda_gb", (r, e), r, (None, None), n, next(ks))
    add("kda_out", (e, d), e, (None, "embed"), n, next(ks))
    p["kda_a_log"] = jnp.log(jax.random.uniform(
        next(ks), (n, h), pt, 1.0, 16.0))
    dt = jnp.exp(jax.random.uniform(next(ks), (n, e), pt, math.log(1e-3),
                                    math.log(1e-1)))
    p["kda_dt_bias"] = dt + jnp.log(-jnp.expm1(-dt))
    p["kda_norm"] = jnp.ones((n, hd), pt)
    ax["kda_a_log"] = ax["kda_dt_bias"] = ax["kda_norm"] = ("layers", None)


def kda_operator(cfg: TransformerConfig, y: jnp.ndarray, lp: Params,
                 state: Optional[jnp.ndarray] = None,
                 conv: Optional[jnp.ndarray] = None,
                 n_new: Optional[jnp.ndarray] = None, layer=None):
    """A KDA layer's operator on a normed input ``y`` [b, s, d] -> (what
    the layer adds to the residual [b, s, d], the delta state' [b, heads,
    dim, dim] float32, the convolutions' last inputs' [b, taps - 1, 3 x
    heads x dim]).  ``state`` None is the PLAIN form over a whole sequence
    from a zero state (`delta_rule.sequence`); with a carried ``state`` and
    ``conv`` one token a row is `delta_rule.step` and a chunk the chunkwise
    form (`delta_rule.chunk`), both advancing a row by its ``n_new`` [b]
    valid tokens only (None: all).  With ``layer`` (one token a row only)
    ``state`` is the STACK of every KDA layer's states [L, b, heads, dim,
    dim] and so is the state handed back, layer ``layer`` of it advanced
    where it lies (`delta_rule.step_in_place`).

    The input and output projections stand under ``projections``; all the
    operator adds beside them (the convolutions, which keep their ``conv``
    part; normalisations, gates, the rule itself, the heads' norm and gate)
    under ``kda`` INSIDE ``attention``."""
    from ..ops import delta_rule
    from ..ops.short_conv import short_conv
    dt, eps = cfg.dtype, norm_eps(cfg)
    h, hd, r = cfg.kda_heads, cfg.kda_head_dim, cfg.kda_gate_rank
    b, s, _ = y.shape
    with jax.named_scope("projections"):
        u = jnp.einsum("bsd,de->bse", y, lp["kda_in"].astype(dt))
    with jax.named_scope("attention"), jax.named_scope("kda"):
        u, conv = short_conv(u, lp["kda_conv"], conv, n_new,
                             activation=jax.nn.silu)
        q, k, v = (t.reshape(b, s, h, hd) for t in jnp.split(u, 3, axis=-1))
        q, k = delta_rule.l2norm(q) * hd ** -0.5, delta_rule.l2norm(k)
        lo = jnp.einsum("bsd,de->bse", y, lp["kda_lo"].astype(dt))
        f = jnp.einsum("bsr,re->bse", lo[..., :r], lp["kda_fb"].astype(dt))
        gate = jnp.einsum("bsr,re->bse", lo[..., r:2 * r],
                          lp["kda_gb"].astype(dt))
        a, beta = delta_rule.gates(
            f.reshape(b, s, h, hd), lo[..., 2 * r:], lp["kda_a_log"],
            lp["kda_dt_bias"].reshape(h, hd))
        if state is None:
            o, state = delta_rule.sequence(q, k, v, a, beta)
        elif s == 1:
            rule = delta_rule.step if layer is None else functools.partial(
                delta_rule.step_in_place, l=layer)
            o, state = rule(
                q[:, 0], k[:, 0], v[:, 0], a[:, 0], beta[:, 0], state,
                live=None if n_new is None else n_new > 0)
            o = o[:, None]
        else:
            o, state = delta_rule.chunk(q, k, v, a, beta, state, n_new)
        o = rmsnorm(o, lp["kda_norm"], eps) * jax.nn.sigmoid(
            gate.reshape(b, s, h, hd).astype(jnp.float32))
    with jax.named_scope("projections"):
        return (jnp.einsum("bse,ed->bsd", o.reshape(b, s, h * hd).astype(dt),
                           lp["kda_out"].astype(dt)), state, conv)


# ---------------------------------------------------------------------------
# a state-space mixer beside attention (`SSM_KINDS`)
# ---------------------------------------------------------------------------

#: operations a float of state a token: decay, the key's outer product with
#: the value added, the query's product summed
_SSM_STATE_OPS = 5


def _ssm_widths(cfg: TransformerConfig) -> Tuple[int, int]:
    """(the mixer's width ``ssm_heads x ssm_head_dim``, its convolution's
    channels: values | keys | queries of every group)."""
    inner = cfg.ssm_heads * cfg.ssm_head_dim
    return inner, inner + 2 * cfg.ssm_groups * cfg.ssm_state


def _ssm_matmul_params(cfg: TransformerConfig) -> int:
    """A state-space mixer's two projections: in (gate | values, keys,
    queries | a step a head) and out."""
    inner, channels = _ssm_widths(cfg)
    return cfg.d_model * (inner + channels + cfg.ssm_heads) \
        + inner * cfg.d_model


def _ssm_own_params(cfg: TransformerConfig) -> int:
    """... and what it holds beside them: the convolution's taps and bias,
    a step bias, a decay and a skip a head, the gated norm's weight."""
    inner, channels = _ssm_widths(cfg)
    return channels * (cfg.ssm_conv_kernel + 1) + 3 * cfg.ssm_heads + inner


def _ssm_state_size(cfg: TransformerConfig) -> int:
    """Floats of state-space state a sequence, summed over the layers."""
    return sum(k in SSM_KINDS for k in cfg.kinds) * cfg.ssm_heads \
        * cfg.ssm_state * cfg.ssm_head_dim


def _init_ssm(add, p: Params, ax: Params, cfg: TransformerConfig, n: int,
              key) -> None:
    """A run's state-space weights, stacked over its ``n`` layers that have
    a mixer: the input projection as one (``ssm_in``: gate | values | keys |
    queries | step), the convolution over values, keys and queries with its
    bias, the step's bias, the decay and the skip a head, the gated norm's
    weight, the output projection.  What decides how long a state remembers
    is drawn as Mamba-2 draws it: ``A_log = log U(1, 16)``, ``dt = exp
    U(log 0.001, log 0.1)``, ``dt_bias = dt + log(-expm1(-dt))``."""
    d, h, pt = cfg.d_model, cfg.ssm_heads, cfg.param_dtype
    inner, channels = _ssm_widths(cfg)
    taps = cfg.ssm_conv_kernel
    ks = iter(jax.random.split(key, 6))
    add("ssm_in", (d, inner + channels + h), d, ("embed", None), n, next(ks))
    add("ssm_conv", (channels, taps), taps, (None, None), n, next(ks))
    add("ssm_out", (inner, d), inner, (None, "embed"), n, next(ks))
    p["ssm_a_log"] = jnp.log(jax.random.uniform(
        next(ks), (n, h), pt, 1.0, 16.0))
    dt = jnp.exp(jax.random.uniform(next(ks), (n, h), pt, math.log(1e-3),
                                    math.log(1e-1)))
    p["ssm_dt_bias"] = dt + jnp.log(-jnp.expm1(-dt))
    p["ssm_conv_b"] = jnp.zeros((n, channels), pt)
    p["ssm_d"] = jnp.ones((n, h), pt)
    p["ssm_norm"] = jnp.ones((n, inner), pt)
    for name in ("ssm_a_log", "ssm_dt_bias", "ssm_conv_b", "ssm_d",
                 "ssm_norm"):
        ax[name] = ("layers", None)


def ssm_operator(cfg: TransformerConfig, y: jnp.ndarray, lp: Params,
                 state: Optional[jnp.ndarray] = None,
                 conv: Optional[jnp.ndarray] = None,
                 n_new: Optional[jnp.ndarray] = None, layer=None):
    """A state-space mixer on a normed input ``y`` [b, s, d] -> (what it
    adds to the residual [b, s, d], the state' [b, heads, state, dim]
    float32, the convolution's last inputs' [b, taps - 1, channels]).
    ``state`` None is the PLAIN form over a whole sequence from a zero state
    (`ssd.sequence`); with a carried ``state`` and ``conv`` one token a row
    is `ssd.step` and a chunk the chunkwise form (`ssd.chunk`), both
    advancing a row by its ``n_new`` [b] valid tokens only (None: all).
    With ``layer`` (one token a row only) ``state`` is the STACK of every
    such layer's states [L, b, heads, state, dim] and so is the state handed
    back, layer ``layer`` of it advanced where it lies
    (`ssd.step_in_place`).

    ALL of it stands under ``ssm``: inside, the two projections under
    ``projections``, the convolution under ``conv``, and the rest (the
    gates, the recurrence, the gated norm) under ``attention``, the part of
    a layer's sequence mixers."""
    from ..ops import ssd
    from ..ops.short_conv import short_conv
    dt, eps = cfg.dtype, norm_eps(cfg)
    h, hd, n, g = (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state,
                   cfg.ssm_groups)
    inner, channels = _ssm_widths(cfg)
    b, s, _ = y.shape
    with jax.named_scope("ssm"):
        with jax.named_scope("projections"):
            u = jnp.einsum("bsd,de->bse", mla.times(y, cfg.ssm_in_scale),
                           lp["ssm_in"].astype(dt))
            if any(m != 1.0 for m in cfg.ssm_scales):   # by segment
                widths = (inner, inner, g * n, g * n, h)
                u = (u.astype(jnp.float32) * jnp.concatenate([
                    jnp.full((w,), m, jnp.float32)
                    for w, m in zip(widths, cfg.ssm_scales)])).astype(dt)
        z, step = u[..., :inner], u[..., inner + channels:]
        u, conv = short_conv(u[..., inner:inner + channels], lp["ssm_conv"],
                             conv, n_new, activation=jax.nn.silu,
                             bias=lp["ssm_conv_b"])
        with jax.named_scope("attention"):
            x = u[..., :inner].reshape(b, s, h, hd)
            B, C = (t.reshape(b, s, g, n)
                    for t in jnp.split(u[..., inner:], 2, axis=-1))
            step, a = ssd.gates(step, lp["ssm_dt_bias"], lp["ssm_a_log"])
            if state is None:
                o, state = ssd.sequence(x, B, C, step, a, lp["ssm_d"])
            elif s == 1:
                rule = ssd.step if layer is None else functools.partial(
                    ssd.step_in_place, l=layer)
                o, state = rule(
                    x[:, 0], B[:, 0], C[:, 0], step[:, 0], a[:, 0],
                    lp["ssm_d"], state,
                    live=None if n_new is None else n_new > 0)
                o = o[:, None]
            else:
                o, state = ssd.chunk(x, B, C, step, a, lp["ssm_d"], state,
                                     n_new)
            o = ssd.gated_norm(o.reshape(b, s, inner), z, lp["ssm_norm"], g,
                               eps)
        with jax.named_scope("projections"):
            return (mla.times(jnp.einsum("bse,ed->bsd", o.astype(dt),
                                       lp["ssm_out"].astype(dt)),
                            cfg.ssm_out_scale), state, conv)


# ---------------------------------------------------------------------------
# layers that read what an earlier layer made (`HANDING_KINDS`), and two
# softmax maps subtracted under a norm (``diff_attn``)
# ---------------------------------------------------------------------------

#: operations a float of selective-scan state a token: the decay's product
#: and exponential, the state's multiply-add, the query's product summed
_MAMBA_STATE_OPS = 6


def _mamba_matmul_params(cfg: TransformerConfig) -> int:
    """A ``"mamba"`` layer's projections: in (input | gate), the input's
    (step | key | query), the step's second, out."""
    d, e, n, r = (cfg.d_model, cfg.mamba_inner, cfg.mamba_state,
                  cfg.mamba_dt_rank)
    return d * 2 * e + e * (r + 2 * n) + r * e + e * d


def _mamba_own_params(cfg: TransformerConfig) -> int:
    """... and what it holds beside them: the convolution's taps and bias,
    the step's bias, a decay a channel a column, a skip a channel."""
    return cfg.mamba_inner * (cfg.mamba_conv_kernel + 3 + cfg.mamba_state)


def _mamba_state_size(cfg: TransformerConfig) -> int:
    """Floats of selective-scan state a sequence, summed over the layers."""
    return cfg.kinds.count("mamba") * cfg.mamba_state * cfg.mamba_inner


def _attn_own_params(cfg: TransformerConfig, kind: str) -> int:
    """What an MHA/GQA layer of ``kind`` holds beside its matrices: the
    projections' biases, a differential layer's four vectors and the scale
    of its pairs' norm."""
    rows = 0 if kind == "cross" else cfg.kv_heads_of(kind) * (
        cfg.key_dim + cfg.value_dim)
    return (cfg.n_heads * cfg.head_dim + rows + cfg.d_model
            if cfg.attn_bias else 0) \
        + (4 * cfg.head_dim + cfg.value_dim if cfg.diff_attn else 0)


def _check_memory(cfg: TransformerConfig) -> None:
    """What a memory model's layers need of a configuration, refused with a
    message where it lacks it."""
    kinds = cfg.kinds
    if "mamba" in kinds and (
            min(cfg.mamba_state, cfg.mamba_expand, cfg.mamba_dt_rank) < 1
            or cfg.mamba_conv_kernel < 2):
        raise ValueError(
            f"layer_kinds {cfg.layer_kinds!r}: a 'mamba' layer needs "
            f"mamba_state, mamba_expand and mamba_dt_rank of at least 1 and "
            f"a mamba_conv_kernel of at least 2")
    for reader, maker in (("gmu", "mamba"), ("cross", "full")):
        if reader in kinds and maker not in kinds[:kinds.index(reader)]:
            raise ValueError(
                f"layer_kinds {cfg.layer_kinds!r}: a {reader!r} layer reads "
                f"what the last {maker!r} layer before it made, and none "
                f"stands before the first")
    if set(kinds) & set(HANDING_KINDS) and (
            cfg.attention != "mha" or cfg.pos_emb == "rope"
            or cfg.index_topk or cfg.pp_stages > 1):
        raise ValueError(
            f"layer_kinds {cfg.layer_kinds!r}: 'mamba', 'gmu' and 'cross' "
            f"layers stand in an MHA/GQA model that turns nothing (a cross "
            f"layer's queries meet another layer's keys as they were "
            f"cached), has no indexer and is no pipeline")
    if cfg.diff_attn and (cfg.n_heads % 2 or cfg.qk_norm or cfg.sink_kinds
                          or cfg.attention != "mha" or cfg.pp_stages > 1
                          or set(kinds) & ({"eva"} | set(SSM_KINDS))):
        raise ValueError(
            "diff_attn subtracts the maps of PAIRS of query heads of an "
            "MHA/GQA block (an even n_heads; n_kv_heads counts pairs): no "
            "per-head norm, sink, summary layer, mixer beside it or pipeline")


def _init_memory(add, p: Params, ax: Params, cfg: TransformerConfig,
                 run: str, key) -> None:
    """A run's selective-scan weights, stacked over its ``"mamba"`` layers
    (in: input | gate; the convolution and its bias; the input's step | key |
    query; the step's second projection and bias; the decay a column a
    channel, channels LAST as the state lies; the skip; out), and its gated
    memory units', over its ``"gmu"`` layers.  What decides how long a state
    remembers is drawn as Mamba draws it: ``A_log = log(1 .. n)`` a channel,
    ``dt = exp U(log 0.001, log 0.1)``, ``dt_bias = dt + log(-expm1(-dt))``."""
    d, e, n, r = (cfg.d_model, cfg.mamba_inner, cfg.mamba_state,
                  cfg.mamba_dt_rank)
    taps, pt = cfg.mamba_conv_kernel, cfg.param_dtype
    ks = iter(jax.random.split(key, 8))
    nm = kind_layers(cfg, run, ("mamba",))
    if nm:
        add("mamba_in", (d, 2 * e), d, ("embed", None), nm, next(ks))
        add("mamba_conv", (e, taps), taps, (None, None), nm, next(ks))
        add("mamba_x", (e, r + 2 * n), e, (None, None), nm, next(ks))
        add("mamba_dt", (r, e), r, (None, None), nm, next(ks))
        add("mamba_out", (e, d), e, (None, "embed"), nm, next(ks))
        dt = jnp.exp(jax.random.uniform(next(ks), (nm, e), pt,
                                        math.log(1e-3), math.log(1e-1)))
        p["mamba_dt_b"] = dt + jnp.log(-jnp.expm1(-dt))
        p["mamba_a_log"] = jnp.broadcast_to(jnp.log(jnp.arange(
            1, n + 1, dtype=pt))[None, :, None], (nm, n, e))
        p["mamba_conv_b"] = jnp.zeros((nm, e), pt)
        p["mamba_d"] = jnp.ones((nm, e), pt)
        ax["mamba_a_log"] = ("layers", None, None)
        ax["mamba_dt_b"] = ax["mamba_conv_b"] = ax["mamba_d"] = (
            "layers", None)
    ng = kind_layers(cfg, run, ("gmu",))
    if ng:
        add("gmu_in", (d, e), d, ("embed", None), ng, next(ks))
        add("gmu_out", (e, d), e, (None, "embed"), ng, next(ks))


def _init_attn_own(p: Params, ax: Params, cfg: TransformerConfig, run: str,
                   n: int, key) -> None:
    """What a run's ``n`` MHA/GQA layers hold beside their matrices: zero
    biases (the rows' over the layers that project rows), a differential
    layer's four vectors (normal, 0.1) and its pairs' norm."""
    pt = cfg.param_dtype
    rows = kind_layers(cfg, run, stack_kinds(cfg, "bk"))
    if cfg.attn_bias:
        for name, count, shape in (
                ("bq", n, (cfg.n_heads, cfg.head_dim)),
                ("bk", rows, (cfg.kv_heads, cfg.key_dim)),
                ("bv", rows, (cfg.kv_heads, cfg.value_dim)),
                ("bo", n, (cfg.d_model,))):
            p[name] = jnp.zeros((count,) + shape, pt)
            ax[name] = ("layers",) + (None,) * len(shape)
    if cfg.diff_attn:
        for name, k in zip(_LAMBDA_KEYS, jax.random.split(key, 4)):
            p[name] = 0.1 * jax.random.normal(k, (n, cfg.head_dim), pt)
        p["diff_norm"] = jnp.ones((n, cfg.value_dim), pt)
        for name in _LAMBDA_KEYS + ("diff_norm",):
            ax[name] = ("layers", None)


def handed(cfg: TransformerConfig, b: int, s: int, rows: bool = False
           ) -> Dict[str, jnp.ndarray]:
    """What the layer loop of a model that `hands_down` carries beside the
    stream, for ``b`` rows of ``s`` tokens, before the first layer:
    ``depth`` (the layer's index among all: a differential layer's
    ``lambda_init``), ``routed`` (a shortcut-connected layer's routed sum [b,
    s, d_model] on its way from the first sublayer to the second), ``m`` (the
    last ``"mamba"`` layer's scan output [b, s,
    mamba_inner], BEFORE its gate: the memory every ``"gmu"`` layer behind
    it reads) and, with ``rows`` (the plain form: nothing is cached), ``k``
    and ``v``, the last ``"full"`` layer's rows for the ``"cross"`` layers
    behind it."""
    out = {"depth": jnp.zeros((), jnp.int32)}
    if cfg.shortcut_moe:    # the routed branch between its two sublayers
        out["routed"] = jnp.zeros((b, s, cfg.d_model), cfg.dtype)
    if "gmu" in cfg.kinds:
        out["m"] = jnp.zeros((b, s, cfg.mamba_inner), cfg.dtype)
    if rows and "cross" in cfg.kinds:
        out.update(
            k=jnp.zeros((b, s, cfg.kv_heads, cfg.key_dim), cfg.dtype),
            v=jnp.zeros((b, s, cfg.kv_heads, cfg.value_dim), cfg.dtype))
    return out


def hand_on(sel, **made):
    """`handed` behind one more layer, which made ``made`` (the keys the
    loop carries alone are kept: a loop's carry keeps its shape)."""
    if not isinstance(sel, dict):
        return sel
    return dict(sel, depth=sel["depth"] + 1,
                **{k: v.astype(sel[k].dtype) for k, v in made.items()
                   if k in sel})


def _pair_queries(cfg: TransformerConfig, q: jnp.ndarray, rotate
                  ) -> jnp.ndarray:
    """Queries [b, s, h, hd], turned where the layer turns -> what attends:
    the same, or, of a differential model, each head against its HALF of the
    pair's key row: head 2p as ``[q | 0]``, head 2p + 1 as ``[0 | q]`` [b,
    s, h, 2 hd], so that the pair's two maps and its 2 hd values are ONE
    grouped-query attention over rows of `key_dim` (a cached row is fetched
    once for both maps)."""
    if rotate is not None:
        q = _rotate_heads(cfg, rotate, q)
    if not cfg.diff_attn:
        return q
    b, s, h, hd = q.shape
    pairs = q.reshape(b, s, h // 2, 2, hd)
    zero = jnp.zeros((b, s, h // 2, hd), q.dtype)
    return jnp.stack(
        [jnp.concatenate([pairs[..., 0, :], zero], axis=-1),
         jnp.concatenate([zero, pairs[..., 1, :]], axis=-1)],
        axis=3).reshape(b, s, h, 2 * hd)


def _pair_keys(cfg: TransformerConfig, k: jnp.ndarray, rotate
               ) -> jnp.ndarray:
    """A differential model's key rows [b, s, pairs, 2 hd]: each half turned
    as the head it is."""
    if rotate is None:
        return k
    b, s, hk, kd = k.shape
    return _rotate_heads(cfg, rotate, k.reshape(
        b, s, 2 * hk, kd // 2)).reshape(k.shape)


def lambda_init(depth) -> jnp.ndarray:
    """A differential layer's fixed part of ``lambda`` by its index among
    all the model's layers: ``0.8 - 0.6 exp(-0.3 depth)``, float32."""
    return 0.8 - 0.6 * jnp.exp(-0.3 * jnp.asarray(depth, jnp.float32))


@jax.named_scope("attention")
@jax.named_scope("diff")
def diff_pairs(cfg: TransformerConfig, attn: jnp.ndarray, lp: Params,
               depth) -> jnp.ndarray:
    """The two maps of each PAIR of heads [b, s, h, vd] -> [b, s, h / 2,
    vd]: ``(1 - lambda_init) rmsnorm(o_1 - lambda o_2)`` with ``lambda =
    exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init(depth)``, float32, the norm
    over the pair's ``vd`` values with a learned scale."""
    b, s, h, vd = attn.shape
    f32 = jnp.float32
    dots = [jnp.sum(lp["diff_lq" + i].astype(f32)
                    * lp["diff_lk" + i].astype(f32)) for i in "12"]
    fixed = lambda_init(depth)
    lam = jnp.exp(dots[0]) - jnp.exp(dots[1]) + fixed
    o = attn.astype(f32).reshape(b, s, h // 2, 2, vd)
    o = o[..., 0, :] - lam * o[..., 1, :]
    return ((1.0 - fixed) * rmsnorm(o, lp["diff_norm"], norm_eps(cfg))
            ).astype(attn.dtype)


def attention_scale(cfg: TransformerConfig) -> Optional[float]:
    """What an MHA/GQA block's scores are multiplied by where that is not
    the attended row's ``width ** -0.5`` (None: it is): a differential
    pair's key row is two heads wide, a score one head's."""
    return cfg.head_dim ** -0.5 if cfg.diff_attn else None


def cross_scope(kind: str):
    """The scope ``cross`` AROUND all of a cross layer's operator (its parts
    keep their own names inside it), nothing for any other layer."""
    return jax.named_scope("cross") if kind == "cross" \
        else contextlib.nullcontext()


def _handing_attention(cfg: TransformerConfig, y, q, k, v, lp: Params,
                       kind: str, sel):
    """`_layer`'s MHA/GQA operator of a model that `hands_down`, the plain
    form -> (what the layer adds to the residual, what it hands on): a
    ``"cross"`` layer attends the rows it is handed, a ``"full"`` layer hands
    its own on."""
    with cross_scope(kind):
        if kind == "cross":
            k, v = sel["k"], sel["v"]
        attn = multi_head_attention(
            q, k, v, causal=cfg.causal, impl=cfg.attention_impl,
            sm_scale=attention_scale(cfg),
            window=cfg.sliding_window if kind == "window" else None)
        delta = _attn_out(cfg, y, attn, lp, sel["depth"])
    return delta, hand_on(sel, **({"k": k, "v": v} if kind == "full"
                                  else {}))


def _memory_layer(cfg: TransformerConfig, x: jnp.ndarray, lp: Params,
                  kind: str, sel):
    """`_layer` for a ``"mamba"`` or a ``"gmu"`` layer over a whole sequence
    (no state carried in) -> (x, aux, what it hands on: a mamba layer its
    scan output)."""
    y = _norm(cfg, x, lp["attn_norm"], lp.get("attn_norm_b"))
    if kind == "mamba":
        delta, m, _, _ = mamba_operator(cfg, y, lp)
        sel = hand_on(sel, m=m)
    else:
        delta, sel = gmu_operator(cfg, y, lp, sel["m"]), hand_on(sel)
    x = x + _post(cfg, delta, lp, "post_attn_norm")
    y = _norm(cfg, x, lp["mlp_norm"], lp.get("mlp_norm_b"))
    z, aux, _ = _ffn(cfg, y, lp)
    return x + _post(cfg, z, lp, "post_mlp_norm"), aux, sel


def mamba_operator(cfg: TransformerConfig, y: jnp.ndarray, lp: Params,
                   state: Optional[jnp.ndarray] = None,
                   conv: Optional[jnp.ndarray] = None,
                   n_new: Optional[jnp.ndarray] = None, layer=None):
    """A selective-scan mixer on a normed input ``y`` [b, s, d] -> (what it
    adds to the residual [b, s, d], its MEMORY ``m`` [b, s, mamba_inner] in
    the compute type: the scan's output BEFORE the gate, what the ``"gmu"``
    layers behind it read, the state' [b, state, inner] float32, the
    convolution's last inputs' [b, taps - 1, inner]).  ``state`` None is the
    PLAIN form over a whole sequence from a zero state
    (`selective_scan.sequence`); with a carried ``state`` and ``conv`` one
    token a row is `selective_scan.step` and a chunk `selective_scan.chunk`,
    both advancing a row by its ``n_new`` [b] valid tokens only (None: all).
    With ``layer`` (one token a row only) ``state`` is the STACK of every such
    layer's states [L, b, 1, state, inner] and so is the state handed back
    (`selective_scan.step_in_place`).

    ALL of it stands under ``ssm``, as a state-space mixer's: inside, the
    projections under ``projections``, the convolution under ``conv``, the
    rest under ``attention``, the recurrence itself under
    ``selective_scan``."""
    from ..ops import selective_scan as scan
    from ..ops.short_conv import short_conv
    dt = cfg.dtype
    e, n, r = cfg.mamba_inner, cfg.mamba_state, cfg.mamba_dt_rank
    s = y.shape[1]
    with jax.named_scope("ssm"):
        with jax.named_scope("projections"):
            u = jnp.einsum("bsd,de->bse", y, lp["mamba_in"].astype(dt))
        a, z = u[..., :e], u[..., e:]
        a, conv = short_conv(a, lp["mamba_conv"], conv, n_new,
                             activation=jax.nn.silu, bias=lp["mamba_conv_b"])
        with jax.named_scope("projections"):
            low = jnp.einsum("bse,er->bsr", a, lp["mamba_x"].astype(dt))
        with jax.named_scope("attention"):
            B, C = low[..., r:r + n], low[..., r + n:]
            step, A = scan.gates(low[..., :r], lp["mamba_dt"],
                                 lp["mamba_dt_b"], lp["mamba_a_log"])
            if state is None:
                m, state = scan.sequence(a, B, C, step, A, lp["mamba_d"])
            elif s == 1:
                rule = scan.step if layer is None else functools.partial(
                    scan.step_in_place, l=layer)
                m, state = rule(
                    a[:, 0], B[:, 0], C[:, 0], step[:, 0], A, lp["mamba_d"],
                    state, live=None if n_new is None else n_new > 0)
                m = m[:, None]
            else:
                m, state = scan.chunk(a, B, C, step, A, lp["mamba_d"], state,
                                      n_new)
            o = (m * jax.nn.silu(z.astype(jnp.float32))).astype(dt)
        with jax.named_scope("projections"):
            return (jnp.einsum("bse,ed->bsd", o, lp["mamba_out"].astype(dt)),
                    m.astype(dt), state, conv)


def gmu_operator(cfg: TransformerConfig, y: jnp.ndarray, lp: Params,
                 m: jnp.ndarray) -> jnp.ndarray:
    """A gated memory unit on a normed input ``y`` [b, s, d] and the memory
    ``m`` [b, s, mamba_inner] of the SAME positions -> what it adds to the
    residual: ``(m * silu(y W_1)) W_2``, elementwise in the position (a
    decode step needs this step's memory alone).  All under ``gmu``."""
    dt = cfg.dtype
    with jax.named_scope("gmu"):
        with jax.named_scope("projections"):
            g = jnp.einsum("bsd,de->bse", y, lp["gmu_in"].astype(dt))
        with jax.named_scope("attention"):
            o = (m.astype(jnp.float32)
                 * jax.nn.silu(g.astype(jnp.float32))).astype(dt)
        with jax.named_scope("projections"):
            return jnp.einsum("bse,ed->bsd", o, lp["gmu_out"].astype(dt))


def _stepped(params, updates, state, given):
    """A step's results: ``params`` with ``updates`` applied and the
    optimizer's new ``state``, each leaf held to the sharding of the leaf it
    replaces (of ``params``, of the state ``given``).  A jit with no
    ``out_shardings`` leaves the partitioner free to return a leaf cut
    another way than it came (a replicated norm scale, updated elementwise,
    comes back cut over ``fsdp`` on the CPU's eight devices), and a loop
    that calls the compiled step on its own results is then refused, or
    compiles a second program.  With no mesh (`jax.set_mesh` around the
    jit, as for `_flash_per_shard`) there is one layout, and nothing is
    added to the program.  (Down here, and two lines where two stood in
    `make_train_step`: `scan_layer_runs`' note on the line numbers.)"""
    import optax
    new = (optax.apply_updates(params, updates), state)
    mesh = jax.sharding.get_abstract_mesh()
    if mesh is None or math.prod(dict(mesh.shape).values() or (1,)) == 1:
        return new
    from jax.experimental.shard_alike import shard_alike
    return jax.tree_util.tree_map(lambda n, g: shard_alike(n, g)[0],
                                  new, (params, given))


def _check_routing(cfg: TransformerConfig) -> None:
    """What identity experts and a shortcut-connected layer need of a
    configuration, refused with a message where it lacks it."""
    if cfg.n_experts and cfg.router not in ("softmax",) + NO_DROP_ROUTERS:
        raise ValueError(f"router={cfg.router!r}: expected 'softmax', "
                         f"'sigmoid' or 'softmax_bias'")
    if cfg.zero_experts and cfg.router != "softmax_bias":
        raise ValueError("zero_experts are outputs of a 'softmax_bias' "
                         "router")
    if cfg.shortcut_moe and (
            cfg.n_layers % 2 or not cfg.reports_load or cfg.n_shared_experts
            or cfg.first_dense_layers or cfg.index_topk or cfg.pp_stages > 1
            or cfg.sandwich_norm or len(set(cfg.kinds)) > 1
            or cfg.kinds[0] not in ("full", "window")):
        raise ValueError(
            "shortcut_moe: n_layers counts SUBLAYERS, two a published layer, "
            "all of one attention kind ('full' or 'window'); the routed "
            "branch drops no token ('sigmoid' or 'softmax_bias'), stands "
            "beside dense feed-forwards (no shared expert, no leading dense "
            "layers, no norm behind a block), and the model has no indexer "
            "and is no pipeline")


def _routed_matmul_params(cfg: TransformerConfig, active: bool) -> float:
    """Matmul parameters of ONE routing layer's routed feed-forward: the
    router (its identity outputs too), the shared experts and the routed
    experts HELD, or with ``active`` the share of a token's top-k choices
    that lands on an expert held here (an identity expert has no matmul: of
    ``n_experts + zero_experts`` outputs chosen alike, ``held`` are)."""
    per = 3 if cfg.activation == "swiglu" else 2
    routed = cfg.n_experts_held
    if active:
        routed = cfg.expert_top_k \
            if routed == cfg.n_experts and not cfg.zero_experts \
            else cfg.expert_top_k * routed / (cfg.n_experts
                                              + cfg.zero_experts)
    return (routed + cfg.n_shared_experts) * cfg.d_model \
        * cfg.expert_ff_dim * per \
        + cfg.d_model * (cfg.n_experts + cfg.zero_experts)


def _init_shortcut(add, p: Params, ax: Params, cfg: TransformerConfig,
                   L: int, key) -> None:
    """A shortcut-connected run's routed branch, stacked over its ROUTING
    sublayers alone (every second one): the router over the experts and the
    identity experts, its correction bias, this chip's experts.  ONE of the
    run's keys."""
    d, f = cfg.d_model, cfg.expert_ff_dim
    n, outs, held = L // 2, cfg.n_experts + cfg.zero_experts, \
        cfg.n_experts_held
    ks = iter(jax.random.split(key, 4))
    add("router", (d, outs), d, ("embed", "expert"), n, next(ks))
    add("we_in", (held, d, f), d, ("expert", "embed", "mlp"), n, next(ks))
    add("we_out", (held, f, d), f, ("expert", "mlp", "embed"), n, next(ks))
    if cfg.activation == "swiglu":
        add("we_gate", (held, d, f), d, ("expert", "embed", "mlp"), n,
            next(ks))
    p["router_bias"] = jnp.zeros((n, outs), cfg.param_dtype)
    ax["router_bias"] = ("layers", "expert")


def _scan_pairs(n: int, xs: Params, layer, carry):
    """`scan_layer_runs` over a run of ``n`` SUBLAYERS of a shortcut-connected
    model: ONE scan step is a published layer, sublayer ``2j`` with the
    routing weights ``j`` (`_ROUTING_KEYS`: stacks over the routing sublayers
    alone) and then sublayer ``2j + 1`` without, each an index INTO the stacks
    (`_scan_part`'s reason).  Both stand in one loop body with the routed
    branch between them (`shortcut`), which depends on neither the second
    attention nor the first dense feed-forward: the compiler may order them as
    it likes, and a deployment hides the experts' exchange behind them."""
    def at(stack, i):
        return jax.lax.dynamic_index_in_dim(stack, i, 0, keepdims=False)

    def step(c, j):
        for i in (0, 1):
            lp = {k: at(v, 2 * j + i) for k, v in xs.items()
                  if k not in _ROUTING_KEYS}
            if i == 0:
                lp.update({k: at(v, j) for k, v in xs.items()
                           if k in _ROUTING_KEYS})
            c = layer(c, lp, j)
        return c, None

    carry, _ = jax.lax.scan(step, carry, jnp.arange(n // 2))
    return carry


def routed_branch(cfg: TransformerConfig, y: jnp.ndarray, lp: Params,
                  valid: Optional[jnp.ndarray] = None):
    """The no-drop routed sum of a normed input ``y`` [b, s, d] -> (out [b, s,
    d], `ops.moe.Load` as the loop carries it): the choice by the model's
    router kind, then `ops.moe.routed_ffn` over the experts held here."""
    from ..ops.moe import routed_ffn, sigmoid_route, softmax_route
    route = {"sigmoid": sigmoid_route, "softmax_bias": softmax_route}[
        cfg.router]         # (`_check_routing` has refused any other)
    b, s, d = y.shape
    flat = y.reshape(b * s, d)
    idx, w = route(flat, lp["router"], lp["router_bias"], cfg.expert_top_k,
                   cfg.routed_scaling_factor)
    w_in, w_gate, w_out = (lp.get(k) for k in cfg.expert_stacks)
    z, load = routed_ffn(
        flat, idx, w, w_in, w_out, w_gate,
        None if valid is None else valid.reshape(b * s),
        expert_offset=cfg.expert_offset,
        identity_from=cfg.n_experts if cfg.zero_experts else None)
    return z.reshape(b, s, d), tuple(load)[:cfg.load_counts]


def shortcut(cfg: TransformerConfig, y: jnp.ndarray, z: jnp.ndarray,
             lp: Params, sel, valid: Optional[jnp.ndarray] = None):
    """A shortcut-connected sublayer's feed-forward half BESIDE its dense
    feed-forward's output ``z`` -> (what the sublayer adds to the stream,
    load, sel).  A sublayer that holds a router computes the routed sum off
    ``y``, the normed input its dense feed-forward read, and HANDS IT ON
    (``sel["routed"]``: it joins the stream a whole sublayer later); the
    sublayer behind adds what it is handed."""
    if "router" in lp:
        m, load = routed_branch(cfg, y, lp, valid)
        return z, load, dict(sel, routed=m.astype(sel["routed"].dtype))
    return z + sel["routed"].astype(z.dtype), no_load(cfg), sel
