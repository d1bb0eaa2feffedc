"""Native C ABI for deployment and train-from-saved-program.

Parity: reference ``paddle/capi/`` (C inference ABI, ``capi.h``) and
``paddle/fluid/train/demo/demo_trainer.cc:1`` (C++ training with no
Python graph build).  The shared library (``paddle_capi.cpp``) embeds a
CPython runtime and drives the jit-compiling Executor through
``_host.py``; native programs include ``paddle_capi.h`` and link
``-lpaddle_tpu_capi -lpython3.x``.  Two demo programs
(``demo/demo_predictor.cc``, ``demo/demo_trainer.cc``) are the
reference demos' analogs and are built+run by ``tests/test_capi.py``.

Build helpers here compile the library/demos on demand with g++
(``native_build``, shared with recordio: the binary is keyed on the
sources' content; no pybind11 — the CPython C API is the binding layer).
"""

import os
import subprocess
import sysconfig
import tempfile

from ..native_build import build_shared

__all__ = ["lib_path", "build_lib", "build_demo", "header_path",
           "native_available"]

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "paddle_capi.cpp")
_HDR = os.path.join(_HERE, "paddle_capi.h")


def header_path():
    return _HDR


def _python_link_flags():
    """-I/-L/-l flags to embed this interpreter (python3-config --embed
    equivalent, resolved from sysconfig so the venv's base is used)."""
    inc = sysconfig.get_path("include")
    libdir = sysconfig.get_config_var("LIBDIR") or "/usr/local/lib"
    ver = sysconfig.get_config_var("LDVERSION") or \
        sysconfig.get_config_var("VERSION")
    return ["-I" + inc], ["-L" + libdir, "-lpython" + ver,
                          "-Wl,-rpath," + libdir, "-ldl", "-lm"]


def build_lib():
    """Compile the shared library (unless a build of exactly these
    sources exists); returns its path."""
    cflags, ldflags = _python_link_flags()
    return build_shared(_HERE, "libpaddle_tpu_capi", [_SRC, _HDR],
                        cflags=cflags, ldflags=ldflags)


def lib_path():
    return build_lib()


def build_demo(name, out_path=None):
    """Compile ``demo/<name>.cc`` against the library; returns the
    binary path."""
    lib = build_lib()
    src = os.path.join(_HERE, "demo", name + ".cc")
    out = out_path or os.path.join(tempfile.gettempdir(),
                                   "pd_" + name + "_%d" % os.getpid())
    cflags, ldflags = _python_link_flags()
    cmd = (["g++", "-O2", "-std=c++17", "-I" + _HERE] + cflags +
           [src, lib, "-Wl,-rpath," + _HERE, "-o", out] + ldflags)
    subprocess.run(cmd, check=True, capture_output=True, text=True)
    return out


def native_available():
    try:
        build_lib()
        return True
    except (OSError, subprocess.CalledProcessError):
        return False
