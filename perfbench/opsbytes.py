"""The table of peaks, and the roofline that operations and bytes are set
against.  The counts themselves (parameters, operations per token, bytes a
decode step reads, a kernel's cost) come from shapes alone and belong to
the configuration's family: ``perfbench/families/<family>.py``.
"""

from __future__ import annotations

import json
import os
from typing import Any, Dict

_HERE = os.path.dirname(os.path.abspath(__file__))


class UnknownDevice(KeyError):
    """A device kind the table of peaks does not name."""


def peaks(device_kind: str) -> Dict[str, float]:
    """Published peaks of one chip.  A kind the table lacks is an error."""
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table or device_kind == "source":
        raise UnknownDevice(f"no published peaks for device kind {device_kind!r}; "
                       f"add it to perfbench/peaks.json with its source")
    return table[device_kind]


def roofline_seconds(flops: float, nbytes: float, peak: Dict[str, float]
                     ) -> Dict[str, Any]:
    t_c = flops / peak["bf16_flops"]
    t_m = nbytes / peak["hbm_bytes_per_s"]
    return {"seconds": max(t_c, t_m),
            "bound": "compute" if t_c >= t_m else "memory"}
