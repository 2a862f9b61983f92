"""Numbers beside limits.  No JAX: the runner imports this."""

from __future__ import annotations

from typing import Any, Dict


def verdict(numbers: Dict[str, float], limits: Dict[str, float],
            sanity: Dict[str, bool]) -> Dict[str, Any]:
    """Correct when every limit has its number, every number is finite and
    inside its limit, and every sanity check held.  ``has_kernel`` is
    reported and not judged: a missing kernel is slow, not wrong."""
    rows, ok = [], bool(limits)
    for name, limit in sorted(limits.items()):
        value = numbers.get(name)
        inside = value is not None and value == value and value <= limit
        rows.append({"number": name, "value": value, "limit": limit,
                     "inside": inside})
        ok = ok and inside
    judged = {k: bool(x) for k, x in sanity.items() if k != "has_kernel"}
    return {"correct": bool(ok and all(judged.values())),
            "compared": rows, "sanity": sanity}
