"""Loader for the native host-path extension (_siddhi_native).

Builds native/columnar.c on first import (cc via setuptools), caches the
shared object under siddhi_tpu/_native_build/<tag>/, and degrades to the
pure-Python encoder when the build fails — with a WARNING that carries the
compiler's stderr, so a host path that quietly turned into Python is visible
in the log. Set SIDDHI_NATIVE=0 to force the Python path on purpose (A/B of
the marshalling hot loop, fallback-parity CI runs).

The cache tag hashes EVERY file the extension is built from (SOURCES), and
the build recompiles from scratch into a tag-private temp directory, so a
loaded binary can only come from the sources in the tree — never from a
stale object file or a header edit the tag did not see."""

from __future__ import annotations

import hashlib
import importlib
import logging
import os
import subprocess
import sys

_log = logging.getLogger("siddhi_tpu")

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_BUILD_ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "_native_build")
_SRC_DIR = os.path.join(_REPO_ROOT, "native")
#: every file the extension is built from (columnar.c #includes the ring
#: core; setup.py carries the compiler flags)
SOURCES = ("columnar.c", "colring_core.h", "setup.py")

native = None


def _src_tag(src_dir: str = _SRC_DIR) -> str | None:
    h = hashlib.sha256()
    try:
        for name in SOURCES:
            with open(os.path.join(src_dir, name), "rb") as f:
                h.update(name.encode() + b"\0" + f.read() + b"\0")
    except OSError:
        return None
    return h.hexdigest()[:16]


_TAG = _src_tag()
_BUILD_DIR = os.path.join(_BUILD_ROOT, _TAG or "nosrc")


def _try_import():
    global native
    if _BUILD_DIR not in sys.path:
        sys.path.insert(0, _BUILD_DIR)
    # the finder caches a nonexistent/empty dir entry; a fresh build would
    # otherwise be invisible until the next interpreter start
    importlib.invalidate_caches()
    import _siddhi_native
    native = _siddhi_native


def _build() -> bool:
    if _TAG is None:  # no sources to build from
        return False
    os.makedirs(_BUILD_DIR, exist_ok=True)
    try:
        subprocess.run(
            [sys.executable, "setup.py", "build_ext", "--force",
             "--build-temp", os.path.join(_BUILD_DIR, "tmp"),
             "--build-lib", _BUILD_DIR],
            cwd=_SRC_DIR, check=True, capture_output=True, text=True,
            timeout=120)
    except subprocess.CalledProcessError as e:
        _log.warning("native extension build failed (rc=%s), using the "
                     "Python encoder and ring; compiler output:\n%s",
                     e.returncode, (e.stderr or e.stdout or "")[-4000:])
        return False
    except (subprocess.SubprocessError, OSError) as e:
        _log.warning("native extension build did not run, using the "
                     "Python encoder and ring: %s", e)
        return False
    # prune superseded hash dirs (and any pre-hash-scheme loose files) so
    # iterative source edits don't accumulate orphaned binaries
    import shutil

    current = os.path.basename(_BUILD_DIR)
    try:
        for entry in os.listdir(_BUILD_ROOT):
            if entry == current:
                continue
            path = os.path.join(_BUILD_ROOT, entry)
            (shutil.rmtree if os.path.isdir(path) else os.remove)(path)
    except OSError:  # pragma: no cover — cleanup is best-effort
        pass
    return True


_DISABLED = os.environ.get("SIDDHI_NATIVE", "").strip() == "0"

if not _DISABLED:
    try:
        _try_import()
    except ImportError:
        if _build():
            try:
                _try_import()
            except ImportError as e:  # pragma: no cover
                _log.warning("native extension built but does not import, "
                             "using the Python encoder and ring: %s", e)


def available() -> bool:
    return native is not None
