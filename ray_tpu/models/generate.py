"""Autoregressive generation with a KV cache, fully jitted.

The serving-side decode path behind the BASELINE north star #5 (p50 TTFT
for TP-sharded replicas): prefill runs the prompt once and materializes
per-layer K/V into a fixed-capacity cache; each decode step then attends
one query position against the cache — O(seq) memory traffic instead of
O(seq²) recompute — and the whole prefill + N-step decode loop compiles
into two XLA programs (`prefill`, `lax.scan` of `decode_step`).  The
cache is a pytree of layer-stacked arrays, so pjit shards it with the
same logical rules as the parameters (heads → tp, batch → dp).

The cache is stored ``[layers, batch, kv_heads, head_dim, max_len]`` —
positions LAST — and every program that takes one extends it IN PLACE:
the whole stacked cache is state of the one layer loop
(:func:`_scan_cached`), written by ``dynamic_update_slice`` and held to
its row-major layout, so a caller that donates its cache pays no copy
at all.  Positions are last because that is the tiling a TPU gives the
array anyway: with ``head_dim`` 64 minor, 64 of 128 lanes (and 25 heads
of 32 sublanes) would be padding, so the device keeps ``max_len`` minor
whatever the logical order says, and a loop that wants another order
converts the whole cache on the way in and on the way out.

Reference: Ray has no model runtime of its own (serving delegates to the
wrapped framework); this module is the TPU-native equivalent of what its
users bring via vLLM/TGI — sized to the in-tree transformer family.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from jax.experimental.layout import Layout, with_layout_constraint

from ..ops.rotary import apply_rotary, rotary_angles
from .transformer import TransformerConfig, _ffn, _layer, _norm, _unembed

Params = Any
KVCache = Dict[str, jnp.ndarray]   # {"k","v": [L, B, hk, hd, max_len], "pos"}


def _cache_shape(cfg: TransformerConfig, batch: int,
                 max_len: int) -> Tuple[int, ...]:
    return (cfg.n_layers, batch, cfg.kv_heads, cfg.head_dim, max_len)


def cache_capacity(cache: KVCache) -> int:
    """``max_len``: the positions a cache holds per row."""
    return cache["k"].shape[-1]


def init_kv_cache(cfg: TransformerConfig, batch: int,
                  max_len: int) -> KVCache:
    shape = _cache_shape(cfg, batch, max_len)
    return {"k": jnp.zeros(shape, cfg.dtype),
            "v": jnp.zeros(shape, cfg.dtype),
            "pos": jnp.zeros((), jnp.int32)}


def _check_decodable(cfg: TransformerConfig) -> None:
    if cfg.pp_stages > 1:
        raise NotImplementedError(
            "KV-cache decode over a pipeline mesh is not supported; "
            "serve pp-sharded models stage-per-gang instead")


def _scan_cached(layers, x: jnp.ndarray, cache: KVCache, layer_fn
                 ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """THE layer loop of every program that writes a KV cache.

    The whole stacked cache ``[L, B, hk, hd, max_len]`` is loop STATE,
    indexed by the layer counter, and the layer weights the scanned
    input: ``layer_fn(x, lp, k_all, v_all, l) -> (x, k_all, v_all)``
    writes its columns into ``k_all[l]`` / ``v_all[l]`` in place.  Passing
    the cache's layers through the scan as inputs and stacking them as
    outputs instead builds a second cache per call, and leaves a donated
    cache argument nothing to alias to.  The carry is held to the
    row-major layout the cache arrives in: left to itself the compiler
    gives loop state the layout its rows are produced in (``head_dim``
    minor) and converts the whole cache before and after the loop.
    → (x, k_all, v_all)."""
    row_major = Layout(major_to_minor=tuple(range(cache["k"].ndim)))

    def step(carry, lp):
        xc, k_all, v_all, l = carry
        k_all = with_layout_constraint(k_all, row_major)
        v_all = with_layout_constraint(v_all, row_major)
        xc, k_all, v_all = layer_fn(xc, lp, k_all, v_all, l)
        return (xc, k_all, v_all, l + 1), None

    (x, k_all, v_all, _), _ = jax.lax.scan(
        step, (x, cache["k"], cache["v"], jnp.zeros((), jnp.int32)), layers)
    return x, k_all, v_all


def _as_columns(rows: jnp.ndarray, dtype) -> jnp.ndarray:
    """New tokens' K or V [B, C, hk, hd] → cache columns [B, hk, hd, C]."""
    return jnp.transpose(rows, (0, 2, 3, 1)).astype(dtype)


def _attend_cached(cfg: TransformerConfig, params: Params, x: jnp.ndarray,
                   cache: KVCache, *, rotate, write, mask
                   ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Run ``x`` [B, C, D] (C new tokens per row) through every layer
    against the cache: each layer writes the new tokens' K/V columns
    (``write(c_all, l, cols [B, hk, hd, C]) -> c_all``), then attends
    dense over layer ``l`` of the cache under ``mask`` [B|1, C, max_len].
    ``rotate`` applies the caller's rotary angles (rope only)."""
    dt = cfg.dtype
    b, c, _ = x.shape
    h, hk, hd = cfg.n_heads, cfg.kv_heads, cfg.head_dim

    def layer(xc, lp, k_all, v_all, l):
        y = _norm(cfg, xc, lp["attn_norm"], lp.get("attn_norm_b"))
        q = jnp.einsum("bsd,dhk->bshk", y, lp["wq"].astype(dt))
        k_new = jnp.einsum("bsd,dhk->bshk", y, lp["wk"].astype(dt))
        v_new = jnp.einsum("bsd,dhk->bshk", y, lp["wv"].astype(dt))
        if cfg.pos_emb == "rope":
            q, k_new = rotate(q), rotate(k_new)
        k_all = write(k_all, l, _as_columns(k_new, k_all.dtype))
        v_all = write(v_all, l, _as_columns(v_new, v_all.dtype))
        ck = jax.lax.dynamic_index_in_dim(k_all, l, 0, keepdims=False)
        cv = jax.lax.dynamic_index_in_dim(v_all, l, 0, keepdims=False)
        # GQA: group query heads over kv heads
        qh = q.reshape(b, c, hk, h // hk, hd)
        scores = jnp.einsum("bskgd,bkdt->bskgt", qh,
                            ck.astype(dt)) / jnp.sqrt(float(hd))
        scores = jnp.where(mask[:, :, None, None, :], scores, -1e30)
        probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
        attn = jnp.einsum("bskgt,bkdt->bskgd", probs.astype(dt),
                          cv.astype(dt))
        attn = attn.reshape(b, c, h, hd)
        xc = xc + jnp.einsum("bshk,hkd->bsd", attn, lp["wo"].astype(dt))
        y2 = _norm(cfg, xc, lp["mlp_norm"], lp.get("mlp_norm_b"))
        z, _ = _ffn(cfg, y2, lp)
        return xc + z, k_all, v_all

    x, k_all, v_all = _scan_cached(params["layers"], x, cache, layer)
    x = _norm(cfg, x, params["final_norm"], params.get("final_norm_b"))
    return x, k_all, v_all


def prefill(params: Params, tokens: jnp.ndarray, cfg: TransformerConfig,
            cache: KVCache) -> Tuple[jnp.ndarray, KVCache]:
    """Run the prompt; → (logits of the LAST position [B, vocab], cache
    holding the prompt's K/V with pos = prompt length)."""
    _check_decodable(cfg)
    b, s = tokens.shape
    dt = cfg.dtype
    if s > cache_capacity(cache):
        raise ValueError(f"prompt length {s} exceeds cache capacity "
                         f"{cache_capacity(cache)}")
    x = params["embed"]["tok"][tokens].astype(dt)
    if cfg.pos_emb == "learned":
        x = x + params["embed"]["pos"][:s].astype(dt)
    cos, sin = (rotary_angles(s, cfg.head_dim, cfg.rope_base)
                if cfg.pos_emb == "rope" else (None, None))

    def layer(h, lp, k_all, v_all, l):
        # K/V for the cache come from the same pre-norm projection the
        # layer itself computes; run the layer for h, re-project for kv
        y = _norm(cfg, h, lp["attn_norm"], lp.get("attn_norm_b"))
        k = jnp.einsum("bsd,dhk->bshk", y, lp["wk"].astype(dt))
        v = jnp.einsum("bsd,dhk->bshk", y, lp["wv"].astype(dt))
        if cfg.pos_emb == "rope":
            k = apply_rotary(k, cos, sin)
        k_all = jax.lax.dynamic_update_slice(
            k_all, _as_columns(k, k_all.dtype)[None], (l, 0, 0, 0, 0))
        v_all = jax.lax.dynamic_update_slice(
            v_all, _as_columns(v, v_all.dtype)[None], (l, 0, 0, 0, 0))
        h, _ = _layer(cfg, h, lp, cos, sin)
        return h, k_all, v_all

    x, k_all, v_all = _scan_cached(params["layers"], x, cache, layer)
    x = _norm(cfg, x, params["final_norm"], params.get("final_norm_b"))
    logits = jnp.einsum("bd,dv->bv", x[:, -1], _unembed(params, cfg))
    return logits.astype(jnp.float32), {
        "k": k_all, "v": v_all, "pos": jnp.asarray(s, jnp.int32)}


def prefill_chunk(params: Params, tokens: jnp.ndarray, cache: KVCache,
                  cfg: TransformerConfig) -> Tuple[jnp.ndarray, KVCache]:
    """Extend the cache with a CHUNK of prompt tokens [B, C] starting at
    ``cache['pos']`` → (logits of the chunk's last position, cache').

    The compile-helper-friendly prefill: one program per (B, C) shape,
    reused across a prompt of any length.  A whole-prompt flash prefill
    compiles a program proportional to the full sequence — the
    llama-1b GQA variant of that compile is a known remote-compile-
    helper killer (SURVEY §9); chunking caps the compiled program at C
    positions.  Chunk attention runs dense against the cache's max_len
    (O(C·max_len) per chunk) — more FLOPs than causal flash, traded for
    a bounded, cacheable compile."""
    _check_decodable(cfg)
    b, c = tokens.shape
    dt = cfg.dtype
    pos = cache["pos"]
    max_len = cache_capacity(cache)
    x = params["embed"]["tok"][tokens].astype(dt)              # [B,C,D]
    if cfg.pos_emb == "learned":
        x = x + jax.lax.dynamic_slice_in_dim(
            params["embed"]["pos"], pos, c, axis=0).astype(dt)
    if cfg.pos_emb == "rope":
        full_cos, full_sin = rotary_angles(max_len, cfg.head_dim,
                                           cfg.rope_base)
        cos = jax.lax.dynamic_slice_in_dim(full_cos, pos, c, axis=0)
        sin = jax.lax.dynamic_slice_in_dim(full_sin, pos, c, axis=0)
    else:
        cos = sin = None
    # mask[i, t]: cached position t visible to chunk token i (causal
    # within the chunk, everything before it fully visible)
    mask = jnp.arange(max_len)[None, :] <= (pos + jnp.arange(c))[:, None]
    x, k_all, v_all = _attend_cached(
        cfg, params, x, cache,
        rotate=lambda t: apply_rotary(t, cos, sin),
        write=lambda c_all, l, cols: jax.lax.dynamic_update_slice(
            c_all, cols[None], (l, 0, 0, 0, pos)),
        mask=mask[None])
    logits = jnp.einsum("bd,dv->bv", x[:, -1], _unembed(params, cfg))
    return logits.astype(jnp.float32), {"k": k_all, "v": v_all,
                                        "pos": pos + c}


# Module-level jit: every prefill_chunked caller shares one trace/compile
# cache (the point of chunking is a bounded, REUSED program).  The cache
# argument is DONATED: the program extends it in place and the caller's
# handle is dead after the call — rebind to the returned cache.
_prefill_chunk_jit = jax.jit(prefill_chunk, static_argnames=("cfg",),
                             donate_argnames=("cache",))

#: The ONE shared chunk program behind every prefill path: legacy
#: `prefill_chunked`, failover `resume_prefill`, AND the serve engine's
#: chunked admission (serve/decode_session.py) all dispatch through this
#: handle, so a replica compiles at most two prefill shapes per model
#: config ([B, chunk] blocks + [B, 1] tail steps) no matter how many
#: prompts, resumes, or admissions it serves.
prefill_chunk_jit = _prefill_chunk_jit


def prefill_chunked(params: Params, tokens: jnp.ndarray,
                    cfg: TransformerConfig, cache: KVCache,
                    *, chunk: int = 512,
                    _jitted=None) -> Tuple[jnp.ndarray, KVCache]:
    """Whole-prompt prefill as ceil(s/chunk) reusable chunk programs
    (at most two compiled shapes: ``chunk`` and the tail remainder).
    Drop-in for :func:`prefill` where compile size must stay bounded."""
    b, s = tokens.shape
    if s > cache_capacity(cache):
        raise ValueError(f"prompt length {s} exceeds cache capacity "
                         f"{cache_capacity(cache)}")
    fn = _jitted or _prefill_chunk_jit
    logits = None
    for off in range(0, s, chunk):
        logits, cache = fn(params, tokens[:, off:off + chunk], cache,
                           cfg=cfg)
    return logits, cache


def resume_prefill(params: Params, tokens: jnp.ndarray,
                   cfg: TransformerConfig, cache: KVCache,
                   *, chunk: int = 32,
                   _jitted=None) -> Tuple[jnp.ndarray, KVCache]:
    """Teacher-forced prefix prefill for decode-session failover.

    A resumed session replays ``prompt + tokens-generated-so-far`` into a
    fresh cache, and that prefix has an *arbitrary* length — one compile
    per resume length (the whole-prompt :func:`prefill` behavior) would
    turn every failover into a compile storm.  This walks the prefix
    through exactly TWO reusable chunk programs: ``[B, chunk]`` blocks,
    then ``[B, 1]`` steps for the remainder — so resuming at any point of
    any stream reuses the same compiled code.

    Greedy replay is deterministic: the logits of the last position are
    (numerically) the same the uninterrupted session would have produced,
    so the argmax — the next token — matches exactly."""
    b, s = tokens.shape
    if s > cache_capacity(cache):
        raise ValueError(f"resume prefix length {s} exceeds cache "
                         f"capacity {cache_capacity(cache)}")
    fn = _jitted or _prefill_chunk_jit
    logits = None
    off = 0
    while off + chunk <= s:
        logits, cache = fn(params, tokens[:, off:off + chunk], cache,
                           cfg=cfg)
        off += chunk
    while off < s:
        logits, cache = fn(params, tokens[:, off:off + 1], cache, cfg=cfg)
        off += 1
    return logits, cache


def decode_step(params: Params, token: jnp.ndarray, cache: KVCache,
                cfg: TransformerConfig) -> Tuple[jnp.ndarray, KVCache]:
    """One token [B] int32 → (next-token logits [B, vocab], cache'): a
    chunk of one."""
    return prefill_chunk(params, token[:, None], cache, cfg)


def init_slot_cache(cfg: TransformerConfig, slots: int,
                    max_len: int) -> KVCache:
    """KV cache for a continuous-batching decode engine: ``slots``
    independent sessions share one batched program, so ``pos`` is a
    per-slot vector instead of the single scalar of
    :func:`init_kv_cache`."""
    shape = _cache_shape(cfg, slots, max_len)
    return {"k": jnp.zeros(shape, cfg.dtype),
            "v": jnp.zeros(shape, cfg.dtype),
            "pos": jnp.zeros((slots,), jnp.int32)}


def cache_insert_slot(slot_cache: KVCache, cache: KVCache,
                      slot: jnp.ndarray) -> KVCache:
    """Write a batch-1 session cache (from :func:`prefill`) into slot
    ``slot`` of a slot-batched cache.  ``slot`` is a TRACED index —
    one jitted program serves every slot, so session admission never
    recompiles."""
    return {
        "k": jax.lax.dynamic_update_slice(
            slot_cache["k"], cache["k"].astype(slot_cache["k"].dtype),
            (0, slot, 0, 0, 0)),
        "v": jax.lax.dynamic_update_slice(
            slot_cache["v"], cache["v"].astype(slot_cache["v"].dtype),
            (0, slot, 0, 0, 0)),
        "pos": jax.lax.dynamic_update_slice(
            slot_cache["pos"],
            jnp.reshape(cache["pos"], (1,)).astype(jnp.int32), (slot,)),
    }


def cache_gather_slot(slot_cache: KVCache, slot: jnp.ndarray,
                      upto: jnp.ndarray) -> KVCache:
    """Extract slot ``slot`` of a slot-batched cache as a batch-1 cache
    TRUNCATED to its first ``upto`` positions — the prefix-reuse
    admission primitive (inverse of :func:`cache_insert_slot`).

    A new session whose prompt shares ``upto`` tokens with a live
    slot's prompt seeds its prefill cache from this copy and chunk-
    prefills only the unshared suffix.  The K/V rows at positions >=
    ``upto`` still hold the donor's LATER tokens, but they sit past the
    returned ``pos`` and every prefill/decode program masks reads to
    positions <= pos — the same stale-rows-are-invisible invariant
    paused slots and rejected speculative writes rely on — and the
    suffix prefill overwrites them before ``pos`` ever reaches them.
    ``slot`` and ``upto`` are TRACED, so one compiled program serves
    every (donor slot, prefix length) pair."""
    one_slot = (slot_cache["k"].shape[0], 1) + slot_cache["k"].shape[2:]
    k = jax.lax.dynamic_slice(slot_cache["k"], (0, slot, 0, 0, 0), one_slot)
    v = jax.lax.dynamic_slice(slot_cache["v"], (0, slot, 0, 0, 0), one_slot)
    return {"k": k, "v": v, "pos": jnp.asarray(upto, jnp.int32)}


def _rotate_slots(x: jnp.ndarray, cos: jnp.ndarray,
                  sin: jnp.ndarray) -> jnp.ndarray:
    """apply_rotary for PER-SLOT positions: cos/sin are [S, 1, 1, hd//2]
    (one angle row per slot) instead of the shared [seq, hd//2] table.
    Same fp32 rotate-half math, so slot decode matches the batch-1
    path bit-for-bit."""
    x32 = x.astype(jnp.float32)
    half = x.shape[-1] // 2
    x1, x2 = x32[..., :half], x32[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                          axis=-1)
    return out.astype(x.dtype)


def _forward_slots(params: Params, tokens: jnp.ndarray, cache: KVCache,
                   cfg: TransformerConfig
                   ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """``tokens`` [S, C]: C tokens per slot, fed at each slot's OWN
    ``pos`` .. ``pos + C - 1`` → (final-norm activations [S, C, D],
    k_all, v_all).  Slots sit at DIFFERENT positions, so each fed
    token's K/V column is written by its own ``dynamic_update_slice``
    (a scatter is not updated in place under the cache's layout).  A
    slice whose start lies past the end is clamped onto the last
    column, where a scatter would have dropped it: a slot's columns are
    therefore written LAST TOKEN FIRST, so the token that belongs in
    the last column overwrites what was clamped onto it.  Only a slot
    already at ``max_len`` loses its last column that way, and that
    slot is finished: nothing reads it again (a prefix match stops
    short of a prompt's last token)."""
    _check_decodable(cfg)
    s, c = tokens.shape
    dt = cfg.dtype
    pos = cache["pos"]                                         # [S]
    max_len = cache_capacity(cache)
    posm = pos[:, None] + jnp.arange(c)[None, :]               # [S, C]
    x = params["embed"]["tok"][tokens].astype(dt)              # [S,C,D]
    if cfg.pos_emb == "learned":
        x = x + params["embed"]["pos"][posm].astype(dt)
    if cfg.pos_emb == "rope":
        full_cos, full_sin = rotary_angles(max_len, cfg.head_dim,
                                           cfg.rope_base)
        cos = full_cos[posm][:, :, None, :]                    # [S,C,1,·]
        sin = full_sin[posm][:, :, None, :]
    else:
        cos = sin = None

    def write(c_all, l, cols):                                 # [S,hk,hd,C]
        for slot in range(s):
            for i in reversed(range(c)):
                c_all = jax.lax.dynamic_update_slice(
                    c_all, cols[None, slot:slot + 1, :, :, i:i + 1],
                    (l, slot, 0, 0, pos[slot] + i))
        return c_all

    # mask[s, i, t]: cached position t visible to fed token i of slot s
    mask = jnp.arange(max_len)[None, None, :] <= posm[:, :, None]
    return _attend_cached(
        cfg, params, x, cache,
        rotate=lambda t: _rotate_slots(t, cos, sin),
        write=write, mask=mask)


def decode_step_slots(params: Params, token: jnp.ndarray, cache: KVCache,
                      active: jnp.ndarray, cfg: TransformerConfig
                      ) -> Tuple[jnp.ndarray, KVCache]:
    """One continuous-batching decode step over ALL slots at once.

    ``token`` [S] int32 (each slot's last token; free/paused slots may
    carry any value), ``cache`` a slot cache with per-slot ``pos`` [S],
    ``active`` [S] bool.  → (logits [S, vocab], cache') where ``pos``
    advances only on active slots.  Inactive slots still compute (the
    batch shape is FIXED — that is what keeps this a single compiled
    program) but their K/V write lands at their un-advanced ``pos`` and
    is overwritten by the next active step before any read, and their
    logits are discarded by the engine.
    """
    x, k_all, v_all = _forward_slots(params, token[:, None], cache, cfg)
    logits = jnp.einsum("bd,dv->bv", x[:, 0], _unembed(params, cfg))
    return logits.astype(jnp.float32), {
        "k": k_all, "v": v_all,
        "pos": cache["pos"] + active.astype(jnp.int32)}


def draft_propose_slots(params: Params, token: jnp.ndarray,
                        cache: KVCache, active: jnp.ndarray,
                        cfg: TransformerConfig, k: int
                        ) -> Tuple[jnp.ndarray, KVCache]:
    """Draft ``k`` greedy tokens per slot in ONE compiled program.

    The proposer side of speculative decoding: a ``lax.scan`` over
    :func:`decode_step_slots` feeds each argmax back in, so one dispatch
    produces ``k`` proposals per slot regardless of ``k`` — on the
    dispatch-bound serving path that is the entire point (k eager draft
    steps would cost k dispatches and erase the win).

    ``token`` [S] int32 (each slot's pending token), ``cache`` the
    DRAFT model's slot cache whose ``pos`` the engine re-syncs from the
    target cache every iteration (rejected speculative writes are then
    overwritten before any masked read — the same invariant paused
    slots rely on).  → (proposals [S, k], cache') with ``pos`` advanced
    by ``k`` on active slots."""

    def step(carry, _):
        tok, c = carry
        logits, c = decode_step_slots(params, tok, c, active, cfg)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        nxt = jnp.where(active, nxt, tok)
        return (nxt, c), nxt

    (_, cache), toks = jax.lax.scan(step, (token, cache), None, length=k)
    return jnp.swapaxes(toks, 0, 1), cache                     # [S, k]


def verify_step_slots(params: Params, tokens: jnp.ndarray,
                      proposals: jnp.ndarray, cache: KVCache,
                      active: jnp.ndarray, cfg: TransformerConfig
                      ) -> Tuple[jnp.ndarray, jnp.ndarray, KVCache]:
    """Speculative-decoding verification: one batched forward over
    ``C`` tokens per slot checks a draft's ``C - 1`` proposals and
    yields 1..C accepted tokens per slot.

    ``tokens`` [S, C] int32 — per slot ``[last_tok, d_1, .., d_{C-1}]``
    (the slot's pending token followed by the draft's proposals);
    ``proposals`` [S, C-1] are the ``d_i`` alone; ``cache`` a slot
    cache with per-slot ``pos`` [S]; ``active`` [S] bool.

    → ``(greedy [S, C], accepted [S], cache')`` where ``greedy[s, i]``
    is the target's argmax after consuming ``tokens[s, :i+1]`` and
    ``accepted[s]`` = 1 + the longest proposal prefix matching that
    greedy chain (clamped to remaining cache capacity) — exactly the
    tokens slot ``s`` emits this iteration, ``greedy[s, :accepted[s]]``.
    ``pos`` advances by ``accepted`` on active slots only.

    Greedy speculative decoding is EXACT: every emitted token is the
    target's own greedy choice given the accepted prefix — the draft
    only decides how many of them one dispatch yields — so the stream
    is byte-identical to plain decode.  K/V of every fed token is
    written at its position; rejected-suffix writes land past the
    advanced ``pos`` and are rewritten (with the true token) before any
    masked read, the same invariant plain decode relies on for paused
    slots.  Writes past ``max_len`` are dropped and ``accepted`` is
    clamped so emission never outruns the cache."""
    pos = cache["pos"]                                         # [S]
    max_len = cache_capacity(cache)
    x, k_all, v_all = _forward_slots(params, tokens, cache, cfg)
    logits = jnp.einsum("bsd,dv->bsv", x, _unembed(params, cfg))
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)     # [S, C]
    ok = (greedy[:, :-1] == proposals).astype(jnp.int32)
    accepted = 1 + jnp.sum(jnp.cumprod(ok, axis=1), axis=1)
    accepted = jnp.minimum(accepted,
                           jnp.maximum(max_len - pos, 1)).astype(jnp.int32)
    adv = jnp.where(active, accepted, 0).astype(jnp.int32)
    return greedy, accepted, {"k": k_all, "v": v_all, "pos": pos + adv}


def _sample(logits: jnp.ndarray, key: jax.Array, greedy: bool,
            temperature: jnp.ndarray, top_k: Optional[int]) -> jnp.ndarray:
    if greedy:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = logits / temperature
    if top_k:
        vals, _ = jax.lax.top_k(logits, top_k)
        cutoff = vals[:, -1:]
        logits = jnp.where(logits < cutoff, -1e30, logits)
    return jax.random.categorical(key, logits).astype(jnp.int32)


@functools.partial(jax.jit,
                   static_argnames=("cfg", "max_new_tokens", "greedy",
                                    "top_k", "total"))
def _generate_impl(params, prompt, temperature, key, *, cfg,
                   max_new_tokens, greedy, top_k, total):
    b = prompt.shape[0]
    cache = init_kv_cache(cfg, b, total)
    logits, cache = prefill(params, prompt, cfg, cache)

    # Token t_i samples from the PREVIOUS logits (prefill's for t_1), so
    # only max_new_tokens - 1 decode passes are needed — decoding after
    # the final sample would be a wasted full forward pass.
    def step(carry, _):
        logits, cache, key = carry
        key, skey = jax.random.split(key)
        tok = _sample(logits, skey, greedy, temperature, top_k)
        logits, cache = decode_step(params, tok, cache, cfg)
        return (logits, cache, key), tok

    (logits, _, key), toks = jax.lax.scan(
        step, (logits, cache, key), None, length=max_new_tokens - 1)
    _, skey = jax.random.split(key)
    last = _sample(logits, skey, greedy, temperature, top_k)
    # scan with length=0 yields a [0, B] array, so this is total for
    # every max_new_tokens >= 1
    toks = jnp.concatenate([toks, last[None]], axis=0)
    return jnp.swapaxes(toks, 0, 1)                            # [B, N]


def generate(params: Params, prompt: jnp.ndarray, *,
             cfg: TransformerConfig, max_new_tokens: int,
             temperature: float = 0.0, top_k: Optional[int] = None,
             max_len: Optional[int] = None,
             key: Optional[jax.Array] = None) -> jnp.ndarray:
    """prompt [B, S] int32 → generated tokens [B, max_new_tokens].

    Greedy when ``temperature == 0`` (default), else temperature /
    top-k sampling.  One compiled program: prefill + scanned decode.
    ``temperature`` is a TRACED input — serving different temperatures
    per request does not recompile (only the greedy/sampled switch,
    top_k, and the shape-bearing knobs are static).
    """
    b, s = prompt.shape
    if max_new_tokens < 1:
        raise ValueError(f"max_new_tokens must be >= 1, "
                         f"got {max_new_tokens}")
    total = max_len or (s + max_new_tokens)
    if total < s + max_new_tokens:
        # a short cache would silently clamp writes onto the last slot
        raise ValueError(
            f"max_len={total} < prompt ({s}) + max_new_tokens "
            f"({max_new_tokens})")
    if cfg.pos_emb == "learned" and total > cfg.max_seq_len:
        # dynamic_slice would silently clamp to the last embedding row
        raise ValueError(
            f"prompt + max_new_tokens ({total}) exceeds the learned "
            f"position table ({cfg.max_seq_len})")
    if key is None:
        key = jax.random.PRNGKey(0)
    # the greedy switch must be a concrete host bool (it selects the
    # compiled program); temperature itself stays traced
    greedy = bool(float(temperature) == 0.0)
    return _generate_impl(
        params, prompt, jnp.asarray(temperature, jnp.float32), key,
        cfg=cfg, max_new_tokens=max_new_tokens,
        greedy=greedy, top_k=top_k, total=total)
