"""Native (C++) runtime components, built on demand with the system
toolchain and loaded via ctypes.

Reference analog: the C++ runtime around the compute path — here the
DataFeed record parser (framework/data_feed.cc).  Build products sit
next to the sources (git-ignored) with a stamp file holding the hash of
the sources and flags they were built from: an artefact whose stamp
does not match — one that travelled with a copied tree, or predates an
edit — is rebuilt, never loaded.  A machine without ``g++`` gets None
(callers fall back to pure Python); a build that fails where ``g++``
exists raises with the compiler's output.
"""
from __future__ import annotations

import ctypes
import hashlib
import os
import shutil
import subprocess
from typing import Optional, Sequence

_DIR = os.path.dirname(os.path.abspath(__file__))
_LIB = None
_TRIED = False


def _build(out_name: str, src_name: str, flags: Sequence[str],
           link_flags: Sequence[str] = (), deps: Sequence[str] = (),
           timeout: int = 180) -> Optional[str]:
    """Build ``out_name`` from ``src_name`` unless its stamp says it was
    built from exactly this source, these ``deps`` (headers) and flags.
    Returns the artefact path, or None when there is no ``g++``."""
    out = os.path.join(_DIR, out_name)
    stamp_path = out + ".stamp"
    h = hashlib.sha256(" ".join([*flags, *link_flags]).encode())
    for name in (src_name, *deps):
        with open(os.path.join(_DIR, name), "rb") as f:
            h.update(f.read())
    stamp = h.hexdigest()
    if os.path.exists(out) and os.path.exists(stamp_path):
        with open(stamp_path, encoding="utf-8") as f:
            if f.read().strip() == stamp:
                return out
    if shutil.which("g++") is None:
        return None
    # libraries follow the source on the link line
    cmd = ["g++", "-O2", *flags, "-o", out,
           os.path.join(_DIR, src_name), *link_flags]
    proc = subprocess.run(cmd, capture_output=True, text=True,
                          timeout=timeout)
    if proc.returncode != 0:
        raise RuntimeError(
            f"native build of {out_name} failed "
            f"(rc={proc.returncode}):\n{proc.stderr[-2000:]}")
    with open(stamp_path, "w", encoding="utf-8") as f:
        f.write(stamp + "\n")
    return out


def _python_flags():
    """Compile/link flags for embedding this interpreter."""
    import sysconfig

    inc = sysconfig.get_path("include")
    libdir = sysconfig.get_config_var("LIBDIR")
    ver = sysconfig.get_config_var("LDVERSION") or \
        sysconfig.get_config_var("VERSION")
    return [f"-I{inc}", f"-L{libdir}", f"-Wl,-rpath,{libdir}",
            f"-lpython{ver}"]


def _build_embedded(out_name: str, src_name: str, flags):
    """An artefact that embeds this interpreter and may include the C
    API header."""
    return _build(out_name, src_name, flags, link_flags=_python_flags(),
                  deps=["paddle_tpu_c_api.h"])


def build_train_demo() -> Optional[str]:
    """Compile the C++ train entry (train_demo.cc); returns the binary
    path or None when there is no toolchain."""
    return _build_embedded("train_demo", "train_demo.cc", [])


def build_c_api() -> Optional[str]:
    """Compile the C inference ABI (capi.cc) into a shared library."""
    return _build_embedded("libpaddle_tpu_c.so", "capi.cc",
                           ["-shared", "-fPIC"])


def datafeed_lib() -> Optional[ctypes.CDLL]:
    """The datafeed parser library, building it on first use."""
    global _LIB, _TRIED
    if _LIB is not None or _TRIED:
        return _LIB
    _TRIED = True
    out = _build("libdatafeed.so", "datafeed.cc", ["-shared", "-fPIC"],
                 timeout=120)
    if out is None:
        return None
    lib = ctypes.CDLL(out)
    lib.parse_records.restype = ctypes.c_long
    lib.parse_records.argtypes = [
        ctypes.c_char_p, ctypes.c_long,
        ctypes.POINTER(ctypes.c_long), ctypes.c_long,
        ctypes.POINTER(ctypes.POINTER(ctypes.c_double)),
        ctypes.c_long]
    _LIB = lib
    return _LIB
