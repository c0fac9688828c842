"""On-demand native builds for the process substrate.

The shim (.so preloaded into plugin processes) and the sequencer (.so
ctypes-loaded into the simulator) compile from `native/` on first use and
cache by source hash, so tests and CLI runs work from a source checkout
without a build step (the reference needs `./setup build`; here cc is
only invoked for the two small runtime libraries).
"""

from __future__ import annotations

import hashlib
import os
import pathlib
import subprocess

_NATIVE = pathlib.Path(__file__).resolve().parents[2] / "native"
# Fixed path inside the checkout (gitignored); names carry the source
# hash, so what loads is always built from the committed sources.
_CACHE = _NATIVE / "build"


def build_lib(src: pathlib.Path, out_name: str, compiler: str,
           extra: list[str]) -> str:
    _CACHE.mkdir(parents=True, exist_ok=True)
    tag = hashlib.sha256(src.read_bytes()).hexdigest()[:16]
    out = _CACHE / f"{out_name}-{tag}.so"
    if not out.exists():
        # Compile to a temp path + atomic rename so a concurrent run never
        # dlopens a partially written .so.
        tmp = _CACHE / f".{out_name}-{tag}.{os.getpid()}.so"
        cmd = [compiler, "-shared", "-fPIC", "-O2", "-o", str(tmp),
               str(src)] + extra
        subprocess.run(cmd, check=True, capture_output=True)
        os.rename(tmp, out)
    return str(out)


def shim_path() -> str:
    return build_lib(_NATIVE / "shim" / "shadow1_shim.c", "shadow1_shim",
                  "cc", ["-ldl", "-lpthread"])


def sequencer_path() -> str:
    return build_lib(_NATIVE / "sequencer.cc", "sequencer", "c++", [])


def build_binary(src: pathlib.Path, name: str) -> str:
    """Compile a plugin test binary (plain cc, no special flags)."""
    _CACHE.mkdir(parents=True, exist_ok=True)
    tag = hashlib.sha256(src.read_bytes()).hexdigest()[:16]
    out = _CACHE / f"{name}-{tag}"
    if not out.exists():
        tmp = _CACHE / f".{name}-{tag}.{os.getpid()}"
        subprocess.run(["cc", "-O1", "-o", str(tmp), str(src),
                        "-lpthread"],
                       check=True, capture_output=True)
        os.rename(tmp, out)
    return str(out)
