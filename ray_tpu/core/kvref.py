"""KV ref markers: large KV values diverted to the object-store path.

The controller KV is control-plane metadata, not a data plane — yet a
20k-task wave was measured pushing 812 MB of function-table blobs
through ``kv_put`` (`state.rpc_attribution`).  Writers now divert
any value above ``kv_inline_max_bytes`` into the object store and store
this small marker in KV instead; readers (``_get_function``, spill
readers) detect the marker and fetch the payload through the normal
object plane (local shm hit or nodelet pull).

The marker is a magic prefix no legitimate value starts with (a NUL
byte followed by a tag) + the raw object id.
"""

from __future__ import annotations

_MAGIC = b"\x00ray-tpu-kvref\x00"


def pack(oid: bytes) -> bytes:
    """Marker bytes for a KV value diverted to object ``oid``."""
    return _MAGIC + oid


def is_ref(value) -> bool:
    return isinstance(value, (bytes, bytearray, memoryview)) \
        and bytes(value[:len(_MAGIC)]) == _MAGIC


def unpack(value) -> bytes:
    """The object id a marker points at (caller checked ``is_ref``)."""
    return bytes(value)[len(_MAGIC):]
