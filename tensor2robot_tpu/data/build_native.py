"""Builds the native data-path library (g++, links libjpeg).

Usage: python -m tensor2robot_tpu.data.build_native
The library is optional: every consumer falls back to the pure-Python
implementations when it is absent or fails to build.
"""

from __future__ import annotations

import hashlib
import os
import subprocess
import sys

_THIS_DIR = os.path.dirname(os.path.abspath(__file__))
SOURCE = os.path.join(_THIS_DIR, "_native", "native_data.cc")
LIBRARY = os.path.join(_THIS_DIR, "_native", "libt2rnative.so")
# No -march=native: the tree (ignored build products included) is copied
# between hosts, and a binary tuned to the build host's CPU dies with
# SIGILL on another (seen on the v5e host, which lacks this sandbox's
# AVX-512 extensions). The source has no intrinsics to lose.
_BUILD_CMD = ("g++", "-O3", "-shared", "-fPIC", "-pthread")
_LINK_LIBS = ("-ljpeg",)
# Sidecar recording the sha256 of the source AND the build command the
# .so came from. Staleness is decided by content hash, NOT mtime
# ordering: a copied or touched .so can carry an mtime newer than an
# updated source while holding pre-update code. Hashing the command too
# retires any binary built under other flags.
HASH_SIDECAR = LIBRARY + ".srchash"


def source_hash() -> str:
  digest = hashlib.sha256(" ".join(_BUILD_CMD + _LINK_LIBS).encode())
  with open(SOURCE, "rb") as f:
    digest.update(f.read())
  return digest.hexdigest()


def library_is_current() -> bool:
  """True iff the built .so exists and matches the current source."""
  if not os.path.exists(LIBRARY):
    return False
  try:
    with open(HASH_SIDECAR) as f:
      recorded = f.read().strip()
  except OSError:
    return False  # no provenance record → rebuild
  return recorded == source_hash()


def build(verbose: bool = True) -> str:
  """Compiles the shared library; returns its path."""
  cmd = [*_BUILD_CMD, SOURCE, "-o", LIBRARY, *_LINK_LIBS]
  result = subprocess.run(cmd, capture_output=True, text=True)
  if result.returncode != 0:
    raise RuntimeError(
        f"native build failed:\n{result.stderr[-2000:]}")
  with open(HASH_SIDECAR, "w") as f:
    f.write(source_hash() + "\n")
  if verbose:
    print(f"Built {LIBRARY}")
  return LIBRARY


def main() -> int:
  build()
  return 0


if __name__ == "__main__":
  sys.exit(main())
