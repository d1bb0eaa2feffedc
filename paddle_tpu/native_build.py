"""Compile-on-first-use for the repo's small C++ libraries (``recordio``,
``capi``).

The built ``.so`` sits next to its sources (gitignored) and is named
after a digest of the sources' CONTENT plus the compile command: a copied
tree (a fresh checkout, the chip tool's snapshot of the disk) promises
neither mtimes nor the absence of a binary built from other sources, so
"reuse when not older than the source" can load a stale library.  A
digest in the name cannot: a changed source or flag is a new file name,
and a name that exists was built from exactly these bytes.
"""

import hashlib
import os
import subprocess
import tempfile

__all__ = ["build_shared"]


def build_shared(out_dir, stem, sources, cflags=(), ldflags=()):
    """Build ``sources[0]`` (a C++ file; the rest of ``sources`` are the
    headers it includes, hashed but not passed to the compiler) into
    ``<out_dir>/_<stem>_<digest>.so`` unless that file already exists;
    returns its path.  Raises ``OSError`` / ``CalledProcessError`` when
    there is no working toolchain."""
    cmd = (["g++", "-O2", "-shared", "-fPIC", "-std=c++17"] + list(cflags)
           + [sources[0]])
    h = hashlib.sha256(repr((cmd[:-1], list(ldflags))).encode())
    for src in sources:
        with open(src, "rb") as f:
            h.update(f.read())
    path = os.path.join(out_dir, "_%s_%s.so" % (stem, h.hexdigest()[:12]))
    if os.path.exists(path):
        return path
    # build to a unique temp name: concurrent first imports (pytest
    # workers, multi-host trainers on a shared FS) must not collide
    fd, tmp = tempfile.mkstemp(dir=out_dir, prefix="_%s_tmp" % stem,
                               suffix=".so")
    os.close(fd)
    try:
        subprocess.run(cmd + ["-o", tmp] + list(ldflags), check=True,
                       capture_output=True, text=True)
        os.replace(tmp, path)
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)
    return path
