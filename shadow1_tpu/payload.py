"""Python binding for the native payload arena (native/payload_arena.cc).

Payload *bytes* live host-side in a C++ refcounted arena; packets on
device carry only a `payload_id`.  This mirrors the reference's split
between Packet metadata and the shared refcounted Payload
(/root/reference/src/main/routing/packet.c:97-100, payload.c) and is the
storage layer the real-code substrate will feed (app write() bytes in,
recv() bytes out).

The shared library builds on demand with g++ through
`substrate.buildlib` (cached by source hash under `native/build/`);
ctypes binds the C ABI -- pybind11 is not part of this toolchain.
"""

from __future__ import annotations

import ctypes
import pathlib

from .substrate import buildlib

_SRC = pathlib.Path(__file__).resolve().parents[1] / "native" / \
    "payload_arena.cc"

_lib = None


def _load():
    global _lib
    if _lib is None:
        lib = ctypes.CDLL(buildlib.build_lib(
            _SRC, "libpayload_arena", "g++", ["-std=c++17"]))
        lib.payload_arena_create.restype = ctypes.c_void_p
        lib.payload_arena_destroy.argtypes = [ctypes.c_void_p]
        lib.payload_arena_put.restype = ctypes.c_uint64
        lib.payload_arena_put.argtypes = [ctypes.c_void_p,
                                          ctypes.c_char_p, ctypes.c_uint64]
        lib.payload_arena_ref.restype = ctypes.c_int
        lib.payload_arena_ref.argtypes = [ctypes.c_void_p, ctypes.c_uint64]
        lib.payload_arena_unref.restype = ctypes.c_int
        lib.payload_arena_unref.argtypes = [ctypes.c_void_p, ctypes.c_uint64]
        lib.payload_arena_size.restype = ctypes.c_int64
        lib.payload_arena_size.argtypes = [ctypes.c_void_p, ctypes.c_uint64]
        lib.payload_arena_get.restype = ctypes.c_int64
        lib.payload_arena_get.argtypes = [ctypes.c_void_p, ctypes.c_uint64,
                                          ctypes.c_char_p, ctypes.c_uint64]
        lib.payload_arena_stats.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_uint64),
            ctypes.POINTER(ctypes.c_uint64), ctypes.POINTER(ctypes.c_uint64)]
        _lib = lib
    return _lib


class PayloadArena:
    """Refcounted byte storage; ids are stable u64 handles (never 0)."""

    def __init__(self):
        self._lib = _load()
        self._h = ctypes.c_void_p(self._lib.payload_arena_create())

    def __del__(self):
        if getattr(self, "_h", None):
            self._lib.payload_arena_destroy(self._h)
            self._h = None

    def put(self, data: bytes) -> int:
        pid = self._lib.payload_arena_put(self._h, data, len(data))
        if pid == 0:
            raise MemoryError("payload arena allocation failed")
        return pid

    def ref(self, pid: int) -> None:
        if self._lib.payload_arena_ref(self._h, pid) != 0:
            raise KeyError(f"invalid payload id {pid}")

    def unref(self, pid: int) -> None:
        if self._lib.payload_arena_unref(self._h, pid) != 0:
            raise KeyError(f"invalid payload id {pid}")

    def get(self, pid: int) -> bytes:
        size = self._lib.payload_arena_size(self._h, pid)
        if size < 0:
            raise KeyError(f"invalid payload id {pid}")
        buf = ctypes.create_string_buffer(max(size, 1))
        n = self._lib.payload_arena_get(self._h, pid, buf, size)
        if n < 0:  # freed between the size check and the copy
            raise KeyError(f"invalid payload id {pid}")
        return buf.raw[:n]

    def stats(self) -> dict:
        live = ctypes.c_uint64()
        live_bytes = ctypes.c_uint64()
        total = ctypes.c_uint64()
        self._lib.payload_arena_stats(self._h, ctypes.byref(live),
                                      ctypes.byref(live_bytes),
                                      ctypes.byref(total))
        return {"live": live.value, "live_bytes": live_bytes.value,
                "total_allocs": total.value}
