"""Weights come from the seed: a family's ``make`` (``perfbench/families/``)
builds them on the device, in one jitted call, in the type they are used in
and in the layout the program takes, from the key this gives.  The plain
reference and the program are both given what it makes, so the reference
uses nothing the program made."""

from __future__ import annotations

import jax


def key_of(seed: int) -> jax.Array:
    """Any whole number, also beyond 32 signed bits."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              (seed >> 31) & 0x7FFFFFFF)
