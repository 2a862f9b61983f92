"""What every family's plain reference shares, and the comparisons that
decide ``correct``.

A family's reference (``perfbench/families/<family>.py``: ``logits``,
``loss``, ``loss_and_grad``) is its model's forward pass, loss and gradients
in straightforward jax.numpy, float32 at ``highest`` matmul precision, on
weights made from the seed; nothing the program computed enters it.

``precision="fp8"`` is the control: the same function with every matmul's
two inputs rounded to float8_e4m3fn first (`_round_inputs`), the nearest
precision below the bfloat16 the configurations state.  A sound run has to
pass the limits and this has to fail them (tests/benchmark, and
tools/outputs_check.py on the chip).
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _round_inputs(precision: str):
    if precision == "float32":
        return lambda x: x.astype(F32)
    if precision == "fp8":
        def r(x):
            x = x.astype(F32)
            # straight-through: forward sees the rounded value, the
            # gradient passes as if it had not been rounded
            return x + jax.lax.stop_gradient(
                x.astype(jnp.float8_e4m3fn).astype(F32) - x)
        return r
    raise ValueError(f"unknown reference precision {precision!r}")


# ------------------------------------------------------------ comparisons

def tree_rel_error(got, want) -> jnp.ndarray:
    """|got - want| / |want| over all leaves together, in float32."""
    num = sum(jnp.sum(jnp.square(g.astype(F32) - w.astype(F32)))
              for g, w in zip(jax.tree_util.tree_leaves(got),
                              jax.tree_util.tree_leaves(want)))
    den = sum(jnp.sum(jnp.square(w.astype(F32)))
              for w in jax.tree_util.tree_leaves(want))
    return jnp.sqrt(num / den)


def logit_numbers(got: jnp.ndarray, want: jnp.ndarray,
                  tokens: jnp.ndarray) -> Dict[str, jnp.ndarray]:
    """``got`` and ``want`` are logits [n, vocab] at the same positions,
    ``tokens`` [n] the token emitted at each.  Returns

    logit_err   root-mean-square difference over the spread of the
                reference's logits;
    token_gap   how far, on average, the emitted token's reference logit
                lies under that position's largest, over the same spread
                (0 where the emitted token is the reference's own choice).
    """
    want = want.astype(F32)
    scale = want.std()
    err = jnp.sqrt(jnp.mean(jnp.square(got.astype(F32) - want))) / scale
    picked = jnp.take_along_axis(want, tokens[:, None], axis=-1)[:, 0]
    gap = (want.max(axis=-1) - picked).mean() / scale
    return {"logit_err": err, "token_gap": gap}
