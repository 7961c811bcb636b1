"""Native-op JIT builder — compile C++ host ops with g++ at first use.

Analog of the reference op_builder (op_builder/builder.py:108 OpBuilder,
jit_load :510): the reference JIT-compiles CUDA/C++ extensions through torch's
cpp_extension; here host ops are plain shared objects built with g++ and bound
through ctypes (pybind11 isn't in the image).  Build artifacts are cached under
the git-ignored ``csrc/_build``, keyed by the source, the compiler flags AND the
host CPU: the build uses ``-march=native``, so a ``.so`` that rode along in a
copy of the tree to another machine must not be ``dlopen``ed there.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import platform
import subprocess
from typing import Optional

from deepspeed_tpu.utils.logging import logger

_CSRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                     "csrc")
_BUILD = os.path.join(_CSRC, "_build")
_cache = {}


def build_error(name: str) -> Optional[str]:
    """Why the native op isn't available (None if it built fine)."""
    try:
        load_op(name)
        return None
    except Exception as e:  # noqa: BLE001
        return str(e)


def _host_cpu() -> str:
    """What ``-march=native`` resolves against: the CPU model and its
    feature flags (``/proc/cpuinfo``), else the platform's own names."""
    try:
        with open("/proc/cpuinfo") as f:
            lines = f.read().splitlines()
    except OSError:
        return f"{platform.machine()} {platform.processor()}"
    keep = {}
    for line in lines:
        key = line.split(":", 1)[0].strip()
        if key in ("model name", "flags", "Features") and key not in keep:
            keep[key] = line
    return "\n".join(keep.values()) or platform.machine()


def load_op(name: str, extra_flags: Optional[list] = None) -> ctypes.CDLL:
    """Compile (if stale) and dlopen ``csrc/<name>.cpp``."""
    if name in _cache:
        return _cache[name]
    src = os.path.join(_CSRC, f"{name}.cpp")
    flags = ["-O3", "-march=native", "-fPIC", "-shared", "-std=c++17",
             "-lpthread"] + (extra_flags or [])
    h = hashlib.sha256()
    with open(src, "rb") as f:
        h.update(f.read())
    h.update("\0".join(flags).encode())
    h.update(_host_cpu().encode())
    os.makedirs(_BUILD, exist_ok=True)
    so = os.path.join(_BUILD, f"{name}-{h.hexdigest()[:16]}.so")
    if not os.path.exists(so):
        cmd = ["g++", "-o", so + ".tmp", src] + flags
        try:
            subprocess.run(cmd, check=True, capture_output=True, text=True)
        except subprocess.CalledProcessError as e:
            raise RuntimeError(
                f"native op {name} failed to compile: {e.stderr}") from e
        os.replace(so + ".tmp", so)
        logger.info(f"built native op {name} -> {so}")
    lib = ctypes.CDLL(so)
    _cache[name] = lib
    return lib
