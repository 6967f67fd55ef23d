"""On-demand compilation of the native components (no pybind11 — pure C ABI
consumed via ctypes, per the environment constraints)."""
from __future__ import annotations

import os
import subprocess
import threading

_lock = threading.Lock()
_HERE = os.path.dirname(os.path.abspath(__file__))
_BUILD_DIR = os.path.join(_HERE, "_build")

SOURCES = {
    "objstore": "object_store.cc",
    "ledger": "ledger.cc",
    "ring": "ring.cc",
    "wire": "wire.cc",
    "net": "net.cc",
}


def build_native(name: str = "objstore", *, force: bool = False) -> str:
    """Compile (if stale, or ``force``) and return the path to lib<name>.so.
    A compiler failure raises ``subprocess.CalledProcessError``."""
    src = os.path.join(_HERE, SOURCES[name])
    out = os.path.join(_BUILD_DIR, f"lib{name}.so")
    with _lock:
        if (
            not force
            and os.path.exists(out)
            and os.path.getmtime(out) >= os.path.getmtime(src)
        ):
            return out
        os.makedirs(_BUILD_DIR, exist_ok=True)
        # per-pid tmp: concurrent agent processes may compile simultaneously;
        # os.replace keeps the publish atomic either way
        tmp = f"{out}.{os.getpid()}.tmp"
        subprocess.run(
            [
                "g++",
                "-O2",
                "-std=c++17",
                "-shared",
                "-fPIC",
                "-o",
                tmp,
                src,
                "-lpthread",
            ],
            check=True,
            capture_output=True,
        )
        os.replace(tmp, out)
    return out


def rebuild_all() -> dict:
    """Compile every component from its committed source, whatever
    ``_build/`` holds: the mtime test above trusts a library that a copied
    tree brought along, which may not be what these sources build.
    Returns {name: path}."""
    return {name: build_native(name, force=True) for name in SOURCES}
