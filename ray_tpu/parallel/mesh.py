"""Device meshes with canonical parallelism axes.

The reference scales by spawning one NCCL rank per GPU process
(`train/torch/config.py:69-144`); the TPU-native design instead lays all
devices out as a single `jax.sharding.Mesh` whose named axes correspond to
parallelism strategies, and lets XLA compile collectives over ICI.  One mesh
spec describes dp/fsdp/tp/pp/sp/ep simultaneously (SURVEY.md §2.4).

Axis conventions (outer → inner, ICI-locality-increasing):

  ``dp``    pure data parallelism (gradient psum; can span DCN across slices)
  ``fsdp``  data parallelism with parameter/optimizer sharding (ZeRO-3)
  ``pp``    pipeline stages (ppermute microbatch handoff)
  ``sp``    sequence/context parallelism (ring attention over an ICI ring)
  ``tp``    tensor parallelism (activation all-gather / reduce-scatter)
  ``ep``    expert parallelism (all_to_all token routing)

Inner axes get the fastest ICI neighborhoods: `jax.experimental.mesh_utils`
`create_device_mesh` arranges physical TPU coords so the last mesh dims are
contiguous on the torus.
"""

from __future__ import annotations

import dataclasses
import math
from typing import TYPE_CHECKING, Dict, List, Optional, Sequence, Tuple

import numpy as np

if TYPE_CHECKING:
    import jax
    from jax.sharding import Mesh

# jax is imported inside the functions that need it: `MeshSpec` is part of
# `ScalingConfig`, which a driver builds, and a driver stays off JAX (the
# chip belongs to the worker that reserved it).

MESH_AXES: Tuple[str, ...] = ("dp", "fsdp", "pp", "sp", "tp", "ep")


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    """Named axis sizes; -1 on at most one axis means "absorb the rest"."""

    dp: int = 1
    fsdp: int = -1
    pp: int = 1
    sp: int = 1
    tp: int = 1
    ep: int = 1

    def sizes(self) -> Dict[str, int]:
        return {a: getattr(self, a) for a in MESH_AXES}

    def resolve(self, n_devices: int) -> Dict[str, int]:
        """Fill the -1 axis so the product equals ``n_devices``."""
        sizes = self.sizes()
        wild = [a for a, s in sizes.items() if s == -1]
        if len(wild) > 1:
            raise ValueError(f"at most one -1 axis allowed, got {wild}")
        fixed = math.prod(s for s in sizes.values() if s != -1)
        if wild:
            if n_devices % fixed != 0:
                raise ValueError(
                    f"{n_devices} devices not divisible by fixed axes "
                    f"product {fixed} ({sizes})")
            sizes[wild[0]] = n_devices // fixed
        elif fixed != n_devices:
            raise ValueError(
                f"mesh spec {sizes} wants {fixed} devices, have {n_devices}")
        return sizes

    @staticmethod
    def parse(text: str) -> "MeshSpec":
        """Parse ``"dp=2,tp=4"`` style strings (CLI / config surface)."""
        kwargs = {}
        for part in text.split(","):
            part = part.strip()
            if not part:
                continue
            axis, _, val = part.partition("=")
            if axis not in MESH_AXES:
                raise ValueError(f"unknown mesh axis {axis!r}")
            kwargs[axis] = int(val)
        return MeshSpec(**kwargs)


def auto_mesh_shape(n_devices: int, model_parallel: int = 1) -> MeshSpec:
    """Heuristic layout: put ``model_parallel`` on tp (innermost, fastest
    ICI), the remainder on fsdp.  Mirrors the common v4/v5 recipe of
    tp-within-host, fsdp-across-hosts."""
    if n_devices % model_parallel != 0:
        raise ValueError(
            f"model_parallel={model_parallel} must divide {n_devices}")
    return MeshSpec(dp=1, fsdp=n_devices // model_parallel, tp=model_parallel)


def mesh_shape_for(spec: MeshSpec, n_devices: int) -> Tuple[int, ...]:
    sizes = spec.resolve(n_devices)
    return tuple(sizes[a] for a in MESH_AXES)


def create_mesh(spec: Optional[MeshSpec] = None,
                devices: Optional[Sequence[jax.Device]] = None,
                *, drop_trivial_axes: bool = False) -> Mesh:
    """Build a `jax.sharding.Mesh` with the canonical axes.

    Uses `mesh_utils.create_device_mesh` when the devices are real TPU chips
    so axis order maps onto the ICI torus (inner axes = nearest neighbors);
    falls back to a plain reshape for host/CPU devices.
    """
    import jax
    from jax.sharding import Mesh
    devices = list(devices) if devices is not None else jax.devices()
    spec = spec or MeshSpec()
    sizes = spec.resolve(len(devices))
    shape = tuple(sizes[a] for a in MESH_AXES)
    if drop_trivial_axes:
        axes = tuple(a for a in MESH_AXES if sizes[a] > 1) or ("dp",)
        shape = tuple(sizes[a] for a in axes)
    else:
        axes = MESH_AXES
    if devices[0].platform == "tpu":
        from jax.experimental import mesh_utils
        dev_array = mesh_utils.create_device_mesh(shape, devices=devices)
    else:
        dev_array = np.asarray(devices).reshape(shape)
    return Mesh(dev_array, axes)


def local_mesh(**axis_sizes: int) -> Mesh:
    """Convenience: mesh over all local devices, e.g. ``local_mesh(tp=4)``;
    unlisted size defaults to fsdp absorbing the remainder."""
    spec = MeshSpec(**axis_sizes) if axis_sizes else MeshSpec()
    return create_mesh(spec)


def slice_topology() -> Dict[str, object]:
    """Describe the attached TPU slice (chip count, coords) for the resource
    spec — the replacement for the reference's GPU-only accelerator detection
    (`python/ray/_private/resource_spec.py:175`)."""
    import jax
    devs = jax.devices()
    info: Dict[str, object] = {
        "platform": devs[0].platform,
        "device_count": len(devs),
        "local_device_count": jax.local_device_count(),
        "process_count": jax.process_count(),
    }
    if devs[0].platform == "tpu":
        kinds = sorted({d.device_kind for d in devs})
        info["device_kind"] = kinds[0] if len(kinds) == 1 else kinds
        coords = [getattr(d, "coords", None) for d in devs]
        if all(c is not None for c in coords):
            arr = np.asarray(coords)
            info["topology"] = tuple(int(x) for x in arr.max(0) - arr.min(0) + 1)
    return info


def pick_divisor_shape(n: int, ndim: int = 2) -> List[int]:
    """Factor ``n`` into ``ndim`` near-equal factors (largest last), used for
    default 2D sp×tp layouts."""
    shape = [1] * ndim
    rem = n
    for i in range(ndim - 1):
        f = int(math.isqrt(rem))
        while f > 1 and rem % f:
            f -= 1
        shape[i] = f
        rem //= f
    shape[-1] = rem
    return shape
