"""Loader for the C++ host-runtime extension (``native/``).

Compiles ``native/pathway_native.cpp`` with g++ on first use and exposes
it; every caller has a Python fallback, and ``PATHWAY_DISABLE_NATIVE=1``
forces it.  The build is ``-march=native``, so the cached ``.so`` under
``native/build/`` is named by a digest of what it was built from and
for — sources, flags, interpreter ABI, this CPU's instruction set: a
build carried over from another machine or an older source is never
loaded, it is rebuilt here.
"""

from __future__ import annotations

import glob
import hashlib
import importlib.util
import logging
import os
import platform
import subprocess
import sysconfig
import threading
from typing import Any

_logger = logging.getLogger("pathway_tpu.native")
_lock = threading.Lock()
_module: Any = None
_tried = False

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
_SRC = os.path.join(_REPO_ROOT, "native", "pathway_native.cpp")
_BUILD_DIR = os.path.join(_REPO_ROOT, "native", "build")
_FLAGS = [
    "-O3", "-march=native", "-funroll-loops", "-shared", "-fPIC", "-std=c++17",
]


def _cpu_identity() -> str:
    """What ``-march=native`` resolves against: the first core's model
    and feature flags (``/proc/cpuinfo``), else the bare architecture."""
    try:
        with open("/proc/cpuinfo") as f:
            lines = [
                line
                for line in f.read().split("\n\n", 1)[0].splitlines()
                if line.startswith(("model name", "flags", "Features"))
            ]
    except OSError:
        lines = []
    return platform.machine() + "\n" + "\n".join(lines)


def _build_key() -> str:
    h = hashlib.sha256()
    for path in sorted(glob.glob(os.path.join(os.path.dirname(_SRC), "*.[ch]*"))):
        with open(path, "rb") as f:
            h.update(f.read())
    h.update(" ".join(_FLAGS).encode())
    h.update((sysconfig.get_config_var("SOABI") or "").encode())
    h.update(_cpu_identity().encode())
    return h.hexdigest()[:16]


def _compile() -> str | None:
    os.makedirs(_BUILD_DIR, exist_ok=True)
    so_path = os.path.join(_BUILD_DIR, f"pathway_native.{_build_key()}.so")
    # cross-PROCESS build lock + atomic rename: spawned cluster workers
    # all race through here on a cold cache; without it two g++ runs write
    # the same .so and a third process dlopens the torn file
    lock_path = os.path.join(_BUILD_DIR, "pathway_native.lock")
    import contextlib

    @contextlib.contextmanager
    def _build_lock():
        try:
            import fcntl

            with open(lock_path, "w") as lf:
                fcntl.flock(lf, fcntl.LOCK_EX)
                try:
                    yield
                finally:
                    fcntl.flock(lf, fcntl.LOCK_UN)
        except ImportError:  # non-POSIX: best effort, rename is still atomic
            yield

    with _build_lock():
        if os.path.exists(so_path):
            return so_path
        include = sysconfig.get_paths()["include"]
        tmp_path = f"{so_path}.{os.getpid()}.tmp"
        cmd = ["g++", *_FLAGS, f"-I{include}", _SRC, "-o", tmp_path]
        try:
            subprocess.run(cmd, check=True, capture_output=True, timeout=300)
            os.replace(tmp_path, so_path)
        except FileNotFoundError:
            _logger.info("native build skipped: no g++ on this machine")
            return None
        except (subprocess.SubprocessError, OSError) as e:
            # g++ is here and the build still failed: the whole host plane
            # is about to run on its Python fallbacks — say so
            stderr = getattr(e, "stderr", None) or b""
            _logger.warning(
                "native build failed, host plane falls back to Python: %r %s",
                e,
                stderr.decode(errors="replace")[-2000:],
            )
            try:
                os.unlink(tmp_path)
            except OSError:
                pass
            return None
        # builds for other sources or machines are dead weight now
        for stale in glob.glob(os.path.join(_BUILD_DIR, "pathway_native.*so")):
            if stale != so_path:
                try:
                    os.unlink(stale)
                except OSError:
                    pass
        return so_path


def load() -> Any:
    """The compiled module, or None (fallback to Python paths)."""
    global _module, _tried
    if _module is not None or _tried:
        return _module
    with _lock:
        if _module is not None or _tried:
            return _module
        _tried = True
        if os.environ.get("PATHWAY_DISABLE_NATIVE") == "1":
            return None
        if not os.path.exists(_SRC):
            return None
        # PATHWAY_NATIVE_SO points at a prebuilt extension (the sanitizer
        # harness builds an ASan/UBSan-instrumented .so out of tree)
        so_path = os.environ.get("PATHWAY_NATIVE_SO") or _compile()
        if so_path is None or not os.path.exists(so_path):
            return None
        try:
            spec = importlib.util.spec_from_file_location("pathway_native", so_path)
            assert spec is not None and spec.loader is not None
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
        except Exception as e:  # noqa: BLE001
            _logger.warning(
                "native load failed, host plane falls back to Python: %r", e
            )
            return None
        # register the value classes the VM needs for type-tagged
        # hashing (Pointer) and Json get/convert semantics.  Local
        # imports: keys/json import this module at top level.
        try:
            from pathway_tpu.internals.json import Json
            from pathway_tpu.internals.keys import Pointer

            mod.set_pointer_type(Pointer)
            mod.set_json_type(Json)
            from pathway_tpu.engine.stream import Update

            mod.set_update_type(Update)
            mod._json_registered = True
        except Exception:  # registration failure only disables fast paths
            mod._json_registered = False
        _module = mod
        return mod
