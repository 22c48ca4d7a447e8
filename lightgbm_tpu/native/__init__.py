"""Native (C++) runtime pieces, compiled on demand with the system g++.

The reference ships its runtime as C++ (src/); here the TPU compute path is
JAX/Pallas and only the genuinely host-sequential pieces go native. Build
is lazy: first use compiles the .cpp next to this file into a cache dir
keyed by source hash; failures degrade to the pure-Python fallbacks.
"""
from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import tempfile
from typing import Optional

from .. import _CACHE_ROOT

# built artefacts live inside the checkout, next to the JAX compile
# cache: nothing prebuilt is read from outside it
_CACHE = os.environ.get("LIGHTGBM_TPU_NATIVE_CACHE",
                        os.path.join(_CACHE_ROOT, "native"))

_libs = {}


def _build(src_path: str, extra_flags=()) -> Optional[str]:
    with open(src_path, "rb") as f:
        src = f.read()
    tag = hashlib.sha256(src + repr(tuple(extra_flags)).encode()).hexdigest()[:16]
    name = os.path.splitext(os.path.basename(src_path))[0]
    out = os.path.join(_CACHE, f"{name}-{tag}.so")
    if os.path.exists(out):
        return out
    os.makedirs(_CACHE, exist_ok=True)
    tmp = tempfile.mktemp(suffix=".so", dir=_CACHE)
    cmd = (["g++", "-O2", "-shared", "-fPIC", "-std=c++17", src_path]
           + list(extra_flags) + ["-o", tmp])
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        os.replace(tmp, out)
        return out
    except Exception:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        return None


def load(name: str, extra_flags=()) -> Optional[ctypes.CDLL]:
    """Load (building if needed) lightgbm_tpu/native/<name>.cpp; None if the
    toolchain is unavailable."""
    key = (name, tuple(extra_flags))
    if key in _libs:
        return _libs[key]
    src = os.path.join(os.path.dirname(__file__), name + ".cpp")
    lib = None
    if os.path.exists(src):
        so = _build(src, extra_flags)
        if so is not None:
            try:
                lib = ctypes.CDLL(so)
            except OSError:
                lib = None
    _libs[key] = lib
    return lib


def python_embed_flags():
    """Compile/link flags for shims that embed CPython (c_api_shim.cpp)."""
    import sysconfig
    inc = sysconfig.get_path("include")
    libdir = sysconfig.get_config_var("LIBDIR") or ""
    ver = sysconfig.get_config_var("LDVERSION") or sysconfig.get_python_version()
    flags = ["-I" + inc]
    if libdir:
        flags += ["-L" + libdir, "-Wl,-rpath," + libdir]
    flags += ["-lpython" + ver]
    return flags


def build_c_api() -> Optional[str]:
    """Build the lib_lightgbm-compatible C ABI shim; returns the .so path."""
    src = os.path.join(os.path.dirname(__file__), "c_api_shim.cpp")
    return _build(src, python_embed_flags())
