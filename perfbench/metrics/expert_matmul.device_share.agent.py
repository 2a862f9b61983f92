"""expert_matmul.device_share.agent: The routed experts' grouped matmuls'
share of all programs' device seconds in the traced window: the ``XLA Ops``
events of XLA's own grouped kernel (``ragged-dot``) and of this repo's
(``grouped_matmul``, `ray_tpu/ops/grouped_matmul.py`) over the ``XLA
Modules`` line's total.  What is left is attention, the dense and shared
feed-forwards, the head and the gathers around the kernel.
"""

from perfbench import xplane

EXPERT_MATMUL = r"ragged-dot|grouped_matmul"


def read(run):
    if run.trace is None:
        return None
    every = xplane.program(run.trace, r"")["device_s"]
    kernels = xplane.op_seconds(run.trace, EXPERT_MATMUL)
    if not every or not kernels:
        return None
    return 100.0 * kernels / every
