"""ray_tpu — a TPU-native distributed computing framework.

Tasks, actors, and a shared-memory object store on a multi-node runtime
(controller + per-node nodelets), with JAX/XLA as the accelerator data plane:
device-mesh collectives over ICI instead of NCCL, pjit/shard_map sharding
instead of DDP wrappers, and TPU-topology-aware placement groups.

Capability mirror of Ray (see SURVEY.md for the layer map); architecture is
TPU-first, not a port.
"""

from .core import accelerator as _accelerator

# before this process's first compile, and before it imports JAX
_accelerator.place_compile_cache()

from .api import (  # noqa: F401
    ActorClass,
    ActorHandle,
    ClientContext,
    RemoteFunction,
    available_resources,
    cancel,
    cluster_resources,
    cpp_actor,
    cpp_function,
    get,
    get_actor,
    get_runtime_context,
    get_tpu_ids,
    init,
    is_initialized,
    kill,
    nodes,
    put,
    remote,
    shutdown,
    timeline,
    wait,
)
from .core.driver import ObjectRef, ObjectRefGenerator  # noqa: F401
from . import exceptions  # noqa: F401
from .dag.node import install_bind as _install_bind

_install_bind()
del _install_bind

__version__ = "0.1.0"
