"""GPipe-style pipeline parallelism over the mesh's ``pp`` axis.

The reference has no pipeline training strategy (SURVEY.md §2.4 row 3 —
"Absent"); this is the TPU-native deliverable for that row.  Design, per
the scaling-book pipelining recipe rather than a torch-style stage-process
topology:

  * model layers are ONE stacked pytree (leading "layers" axis); sharding
    that axis over ``pp`` gives each device-group a contiguous stage slab —
    stage assignment is a `device_put`, not a process topology,
  * execution runs under `jax.shard_map` **manual only over pp**
    (``axis_names={"pp"}``): inside the pipeline body, tp/fsdp/sp stay
    auto-sharded by GSPMD, so PP composes with TP/FSDP for free,
  * microbatches flow stage→stage via `lax.ppermute` in a `lax.scan` over
    ``n_micro + n_stages - 1`` ticks (the GPipe schedule with its bubble),
  * the last stage's outputs are broadcast with a `psum` so the caller sees
    a pp-invariant result (loss/unembed run replicated over pp).

The microbatch *state* is an arbitrary pytree (activations plus e.g. a MoE
aux-loss scalar); every leaf of ``x_mb`` carries a leading ``n_micro`` axis.

Differentiable end-to-end: scan + ppermute + psum all have transpose rules,
so one `jax.grad` over the wrapped forward is pipeline-parallel backprop.
"""

from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

_tmap = jax.tree_util.tree_map


def _index(tree: Any, i) -> Any:
    return _tmap(lambda a: jax.lax.dynamic_index_in_dim(a, i, 0,
                                                        keepdims=False), tree)


def _update(tree: Any, leaf_tree: Any, i) -> Any:
    return _tmap(lambda a, v: jax.lax.dynamic_update_index_in_dim(a, v, i, 0),
                 tree, leaf_tree)


def _select(pred, a: Any, b: Any) -> Any:
    return _tmap(lambda x, y: jnp.where(pred, x, y), a, b)


def _pipeline_body(stage_params: Any, x_mb: Any, *,
                   stage_fn: Callable[[Any, Any], Any],
                   n_stages: int, n_micro: int, axis: str,
                   boundary_f32: bool) -> Any:
    """Per-stage program (runs under shard_map, manual over ``axis``).

    stage_params: this stage's slab (leading dim = layers/stage);
    x_mb: pytree of [n_micro, ...] microbatches, identical on every stage.
    ``boundary_f32`` keeps the carried state fp32 across the manual
    ppermute/psum/select boundary ops — the CPU backend's SPMD partitioner
    aborts on bf16 collectives inside a partial-manual region ("invalid
    binary opcode copy"); TPU keeps the narrow dtype for ICI bandwidth.
    """
    stage = jax.lax.axis_index(axis)
    last = n_stages - 1
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
    dtypes = _tmap(lambda a: a.dtype, x_mb)
    if boundary_f32:
        x_mb = _tmap(lambda a: a.astype(jnp.float32), x_mb)

    def _wide(tree):
        return (_tmap(lambda a: a.astype(jnp.float32), tree)
                if boundary_f32 else tree)

    def _narrow(tree):
        return (_tmap(lambda a, dt: a.astype(dt), tree, dtypes)
                if boundary_f32 else tree)

    def tick(carry, t):
        state, outputs = carry
        # stage 0 ingests microbatch t while it exists; later ticks feed
        # garbage that never reaches an output slot (write is guarded)
        inject = _index(x_mb, jnp.clip(t, 0, n_micro - 1))
        h = _select(stage == 0, inject, state)
        y = _wide(stage_fn(stage_params, _narrow(h)))
        out_t = t - last
        idx = jnp.clip(out_t, 0, n_micro - 1)
        write = jnp.logical_and(stage == last, out_t >= 0)
        outputs = _update(outputs, _select(write, y, _index(outputs, idx)),
                          idx)
        state = jax.lax.ppermute(y, axis, perm)
        return (state, outputs), None

    # The carry becomes pp-varying after the first ppermute/where; mark the
    # (invariant-zero) initial carry as varying so scan's types line up.
    carry0 = _tmap(lambda a: jax.lax.pcast(a, (axis,), to="varying"),
                   (_index(_tmap(jnp.zeros_like, x_mb), 0),
                    _tmap(jnp.zeros_like, x_mb)))
    (_, outputs), _ = jax.lax.scan(
        tick, carry0, jnp.arange(n_micro + n_stages - 1))
    # outputs is nonzero only on the last stage: psum broadcasts it
    return _narrow(jax.lax.psum(outputs, axis))


def pipeline_apply(stage_fn: Callable[[Any, Any], Any],
                   stacked_params: Any, x_mb: Any, *,
                   n_stages: int, n_micro: int, mesh=None,
                   axis: str = "pp") -> Any:
    """Run microbatches through a pipelined stack of layers.

    stage_fn(stage_slab, state) applies one stage's worth of layers
    (typically a `lax.scan` over the slab's leading dim) to one microbatch
    state.  ``stacked_params`` leaves have a leading layers axis divisible
    by ``n_stages``; every leaf of ``x_mb`` has leading dim ``n_micro``.
    Returns the output microbatch pytree (leading dim ``n_micro``).
    """
    if mesh is None:
        mesh = jax.sharding.get_abstract_mesh()
    # Platform from the mesh when concrete: it is the one the program
    # will run on, which the process default need not be.
    try:
        platform = mesh.devices.flat[0].platform
    except (AttributeError, ValueError):  # AbstractMesh
        platform = jax.default_backend()
    body = functools.partial(_pipeline_body, stage_fn=stage_fn,
                             n_stages=n_stages, n_micro=n_micro, axis=axis,
                             boundary_f32=platform != "tpu")
    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(_tmap(lambda _: P(axis), stacked_params),
                  _tmap(lambda _: P(), x_mb)),
        out_specs=_tmap(lambda _: P(), x_mb),
        axis_names={axis})
    return fn(stacked_params, x_mb)


def microbatch(x: jnp.ndarray, n_micro: int) -> jnp.ndarray:
    """[b, ...] → [n_micro, b/n_micro, ...]."""
    b = x.shape[0]
    if b % n_micro:
        raise ValueError(f"batch {b} not divisible by {n_micro} microbatches")
    return x.reshape((n_micro, b // n_micro) + x.shape[1:])


def unmicrobatch(x: jnp.ndarray) -> jnp.ndarray:
    """Inverse of `microbatch`."""
    return x.reshape((x.shape[0] * x.shape[1],) + x.shape[2:])
