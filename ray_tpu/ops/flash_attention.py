"""Blockwise fused attention (flash attention) as a Pallas TPU kernel.

The reference framework has no fused attention of its own — it delegates all
model math to torch (SURVEY.md §2.3); in a TPU-native stack the attention
inner loop is the single hottest op, so it gets a hand-written kernel:

  * online-softmax forward with fp32 accumulators in VMEM scratch,
  * custom-VJP backward (separate dq and dk/dv kernels),
  * grouped-query attention handled by index maps (no KV repetition),
  * causal blocks above the diagonal skipped via ``pl.when``.

Inputs are ``[batch, seq, heads, head_dim]`` (framework activation layout);
the kernel operates in ``[batch, heads, seq, head_dim]``.  bf16 in/out, fp32
softmax statistics.  Sequence length must be divisible by the block sizes —
callers (`ray_tpu.ops.attention.multi_head_attention`) fall back to the
reference jnp implementation otherwise.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Test-only switch: RAY_TPU_PALLAS_INTERPRET=1 runs the kernels through the
# Pallas interpreter on any backend, so the suite proves the kernel math on
# the CPU.  Nothing that runs on the chip sets it (chip_smoke.py refuses to
# start with it).
import os as _os


def _interpret() -> bool:
    return _os.environ.get("RAY_TPU_PALLAS_INTERPRET") == "1"


DEFAULT_BLOCK_Q = 256
DEFAULT_BLOCK_K = 512
_NEG_INF = -1e30  # avoids -inf - -inf = nan in the online softmax


def _env_block(name: str, default: int) -> int:
    raw = _os.environ.get(name)
    if raw is None:
        return default
    try:
        val = int(raw)
    except ValueError:
        raise ValueError(f"{name}={raw!r}: must be a positive integer")
    if val < 8:
        raise ValueError(f"{name}={val}: flash block sizes must be >= 8")
    return val


def _default_blocks() -> Tuple[int, int]:
    """Block sizes resolve at trace time, overridable via env
    (RAY_TPU_FLASH_BLOCK_Q/K) for on-chip tuning sweeps.  Defaults were
    measured on v5e (gpt2-small train step): 128x128 made the grid so
    fine (b*h*8*8 = 6k steps per layer call) that per-step fixed costs
    beat the MXU work; 256x512 keeps VMEM modest (score block = 512 KiB
    fp32) with 16x fewer grid steps."""
    return (_env_block("RAY_TPU_FLASH_BLOCK_Q", DEFAULT_BLOCK_Q),
            _env_block("RAY_TPU_FLASH_BLOCK_K", DEFAULT_BLOCK_K))


def fit_block(block: int, s: int) -> int:
    """Largest block <= ``block`` that divides ``s`` (halving search, so a
    128-aligned sequence shorter than the default still lands on a
    128-multiple block instead of being rejected)."""
    b = min(block, s)
    while b > 1 and s % b:
        b //= 2
    return b


def _dims(q, k):
    b, h, s_q, d = q.shape
    h_kv, s_kv = k.shape[1], k.shape[2]
    assert h % h_kv == 0, f"query heads {h} not a multiple of kv heads {h_kv}"
    return b, h, h_kv, h // h_kv, s_q, s_kv, d


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref, *,
                sm_scale, causal, block_q, block_k, num_k, q_offset):
    qi, ki = pl.program_id(2), pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    live = ((ki * block_k <= qi * block_q + block_q - 1 + q_offset)
            if causal else (ki >= 0))

    @pl.when(live)
    def _compute():
        # MXU-native precision: keep inputs in their storage dtype (bf16)
        # and accumulate fp32 via preferred_element_type — casting inputs
        # to fp32 first would force the multi-pass fp32 MXU path (~4-8x
        # slower; measured 0.9x vs unfused attention on v5e before this).
        s = jax.lax.dot_general(q_ref[0, 0], k_ref[0, 0],
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * sm_scale
        if causal:
            rows = q_offset + qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            cols = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(rows >= cols, s, _NEG_INF)
        m_prev = m_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        # A row fully masked within a live block (causal with s_q > s_kv:
        # rows above the diagonal of their first k-block) has m_new ==
        # _NEG_INF, making exp(s - m_new) == 1 for every masked column —
        # zero those rows instead of averaging V uniformly.
        p = jnp.where(m_new <= _NEG_INF * 0.5, 0.0, jnp.exp(s - m_new))
        l_ref[...] = jnp.broadcast_to(
            alpha * l_ref[:, :1] + jnp.sum(p, axis=1, keepdims=True),
            l_ref.shape)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        pv = jax.lax.dot_general(p.astype(v_ref.dtype), v_ref[0, 0],
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha + pv

    @pl.when(ki == num_k - 1)
    def _finish():
        l = l_ref[:, :1]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[...] / safe_l).astype(o_ref.dtype)
        # Dead rows (m still _NEG_INF) get lse = 0 so the backward kernels'
        # exp(s - lse) = exp(_NEG_INF) underflows to zero gradient; the
        # natural m + log(l) would be ~ -1e30 - 69, making s - lse positive.
        m = m_ref[:, :1]
        lse_ref[0, 0] = jnp.where(
            m <= _NEG_INF * 0.5, 0.0,
            m + jnp.log(jnp.maximum(l_ref[:, :1], 1e-30)))


def _flash_fwd(q, k, v, causal, sm_scale, block_q, block_k):
    b, h, h_kv, group, s_q, s_kv, d = _dims(q, k)
    num_q, num_k = s_q // block_q, s_kv // block_k
    grid = (b, h, num_q, num_k)

    kernel = functools.partial(
        _fwd_kernel, sm_scale=sm_scale, causal=causal,
        block_q=block_q, block_k=block_k, num_k=num_k,
        q_offset=s_kv - s_q)
    out_shapes = (
        jax.ShapeDtypeStruct((b, h, s_q, d), q.dtype),
        jax.ShapeDtypeStruct((b, h, s_q, 1), jnp.float32),
    )
    o, lse = pl.pallas_call(
        kernel,
        # the HLO instruction takes this name, and a profiler trace's
        # `XLA Ops` events are named by instruction: the kernel shows as
        # itself there and not as `checkpoint.19` (scope_probe, PERF.md)
        name="flash_attention_fwd",
        grid=grid,
        interpret=_interpret(),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda b_, h_, qi, ki: (b_, h_, qi, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b_, h_, qi, ki, g=group: (b_, h_ // g, ki, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b_, h_, qi, ki, g=group: (b_, h_ // g, ki, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda b_, h_, qi, ki: (b_, h_, qi, 0)),
            pl.BlockSpec((1, 1, block_q, 1),
                         lambda b_, h_, qi, ki: (b_, h_, qi, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
        ],
        out_shape=out_shapes,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
    )(q, k, v)
    return o, lse


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   dq_acc, *, sm_scale, causal, block_q, block_k, num_k,
                   q_offset):
    qi, ki = pl.program_id(2), pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    live = ((ki * block_k <= qi * block_q + block_q - 1 + q_offset)
            if causal else (ki >= 0))

    @pl.when(live)
    def _compute():
        # bf16 MXU inputs + fp32 accumulation throughout (see _fwd_kernel)
        k = k_ref[0, 0]
        lse = lse_ref[0, 0]
        delta = delta_ref[0, 0]
        s = jax.lax.dot_general(q_ref[0, 0], k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * sm_scale
        if causal:
            rows = q_offset + qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            cols = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(rows >= cols, s, _NEG_INF)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(do_ref[0, 0], v_ref[0, 0],
                                 (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta) * sm_scale).astype(k.dtype)
        dq_acc[...] += jax.lax.dot_general(ds, k, (((1,), (0,)), ((), ())),
                                           preferred_element_type=jnp.float32)

    @pl.when(ki == num_k - 1)
    def _finish():
        dq_ref[0, 0] = dq_acc[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc, *,
                    sm_scale, causal, block_q, block_k, num_q, group,
                    q_offset):
    ki, gi, qi = pl.program_id(2), pl.program_id(3), pl.program_id(4)

    @pl.when((qi == 0) & (gi == 0))
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    live = ((qi * block_q + block_q - 1 + q_offset >= ki * block_k)
            if causal else (qi >= 0))

    @pl.when(live)
    def _compute():
        # bf16 MXU inputs + fp32 accumulation throughout (see _fwd_kernel)
        q = q_ref[0, 0]
        do = do_ref[0, 0]
        lse = lse_ref[0, 0]
        delta = delta_ref[0, 0]
        s = jax.lax.dot_general(q, k_ref[0, 0], (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * sm_scale
        if causal:
            rows = q_offset + qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            cols = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(rows >= cols, s, _NEG_INF)
        p = jnp.exp(s - lse)                                   # [bq, bk]
        dv_acc[...] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)                # [bk, d]
        dp = jax.lax.dot_general(do, v_ref[0, 0], (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta) * sm_scale).astype(q.dtype)
        dk_acc[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)                # [bk, d]

    @pl.when((qi == num_q - 1) & (gi == group - 1))
    def _finish():
        dk_ref[0, 0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


def _flash_bwd(q, k, v, o, lse, do, causal, sm_scale, block_q, block_k):
    b, h, h_kv, group, s_q, s_kv, d = _dims(q, k)
    num_q, num_k = s_q // block_q, s_kv // block_k
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1, keepdims=True)                    # [b, h, s_q, 1]

    sem = ("parallel", "parallel", "parallel", "arbitrary")
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, sm_scale=sm_scale, causal=causal,
                          block_q=block_q, block_k=block_k, num_k=num_k,
                          q_offset=s_kv - s_q),
        name="flash_attention_dq",
        grid=(b, h, num_q, num_k),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda b_, h_, qi, ki: (b_, h_, qi, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b_, h_, qi, ki, g=group: (b_, h_ // g, ki, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b_, h_, qi, ki, g=group: (b_, h_ // g, ki, 0)),
            pl.BlockSpec((1, 1, block_q, d), lambda b_, h_, qi, ki: (b_, h_, qi, 0)),
            pl.BlockSpec((1, 1, block_q, 1),
                         lambda b_, h_, qi, ki: (b_, h_, qi, 0)),
            pl.BlockSpec((1, 1, block_q, 1),
                         lambda b_, h_, qi, ki: (b_, h_, qi, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d),
                               lambda b_, h_, qi, ki: (b_, h_, qi, 0)),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=sem),
        interpret=_interpret(),
    )(q, k, v, do, lse, delta)

    sem5 = ("parallel", "parallel", "parallel", "arbitrary", "arbitrary")
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, sm_scale=sm_scale, causal=causal,
                          block_q=block_q, block_k=block_k, num_q=num_q,
                          group=group, q_offset=s_kv - s_q),
        name="flash_attention_dkv",
        grid=(b, h_kv, num_k, group, num_q),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda b_, h2, ki, g_, qi, G=group: (b_, h2 * G + g_, qi, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b_, h2, ki, g_, qi: (b_, h2, ki, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b_, h2, ki, g_, qi: (b_, h2, ki, 0)),
            pl.BlockSpec((1, 1, block_q, d),
                         lambda b_, h2, ki, g_, qi, G=group: (b_, h2 * G + g_, qi, 0)),
            pl.BlockSpec((1, 1, block_q, 1),
                         lambda b_, h2, ki, g_, qi, G=group: (b_, h2 * G + g_, qi, 0)),
            pl.BlockSpec((1, 1, block_q, 1),
                         lambda b_, h2, ki, g_, qi, G=group: (b_, h2 * G + g_, qi, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b_, h2, ki, g_, qi: (b_, h2, ki, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda b_, h2, ki, g_, qi: (b_, h2, ki, 0)),
        ],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=sem5),
        interpret=_interpret(),
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# custom-vjp wrapper (operates in [b, h, s, d])
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q, k, v, causal, sm_scale, block_q, block_k):
    o, _ = _flash_fwd(q, k, v, causal, sm_scale, block_q, block_k)
    return o


def _flash_fwd_rule(q, k, v, causal, sm_scale, block_q, block_k):
    o, lse = _flash_fwd(q, k, v, causal, sm_scale, block_q, block_k)
    return o, (q, k, v, o, lse)


def _flash_bwd_rule(causal, sm_scale, block_q, block_k, res, do):
    q, k, v, o, lse = res
    dq, dk, dv = _flash_bwd(q, k, v, o, lse, do, causal, sm_scale,
                            block_q, block_k)
    return dq, dk, dv


_flash.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True,
                    sm_scale: Optional[float] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None) -> jnp.ndarray:
    """Fused attention over ``[batch, seq, heads, head_dim]`` inputs.

    KV heads may be a divisor of query heads (GQA/MQA).  Differentiable via
    flash backward kernels.  Block sizes default from `_default_blocks()`
    (env-tunable) when not given, and are clamped (halving search) to the
    largest divisor of each seq length; raises only when no divisor >= 8
    exists — use `multi_head_attention` for automatic fallback.
    """
    dq, dk_ = _default_blocks()
    if block_q is None:
        block_q = dq
    if block_k is None:
        block_k = dk_
    s_q, s_kv = q.shape[1], k.shape[1]
    bq, bk = fit_block(block_q, s_q), fit_block(block_k, s_kv)
    if bq < 8 or bk < 8:   # no MXU-reasonable divisor exists
        raise ValueError(
            f"seq lengths ({s_q}, {s_kv}) have no block divisor >= 8 "
            f"under ({block_q}, {block_k})")
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    # the kernels feed q/k/v straight into MXU dots in their storage dtype
    # (bf16 in + fp32 accumulation); normalize mixed-dtype inputs (e.g. an
    # fp32 query against a bf16 KV cache) to the query's dtype up front
    k = k.astype(q.dtype)
    v = v.astype(q.dtype)
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    out = _flash(qt, kt, vt, causal, sm_scale, bq, bk)
    return jnp.swapaxes(out, 1, 2)
