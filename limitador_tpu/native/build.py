"""Shared builder for the vendored native libraries.

``hostpath`` (native/hostpath.cc) and ``h2ingress`` (native/h2ingress.cc)
used to carry copy-pasted digest/stamp/compile logic; this module is the
single implementation both bind through. One :class:`NativeLib` per
shared object:

- **Content-based staleness**: the built ``.so`` is valid only while a
  stamp file carries the sha256 of every source file plus the compile
  flags (mtime ordering is unreliable across checkouts, and a flag
  change must rebuild too).
- **Compiler search**: ``$CXX`` when set, then ``g++``, then ``clang++``
  — the first candidate that produces a binary wins; every failed
  attempt's error is kept so the surfaced build error names what was
  tried.
- **Per-library error surface**: ``build_status()`` reports, for every
  registered library, whether it loaded and the build error string when
  it did not — served under ``GET /debug/stats`` (server/http_api.py)
  so a silently-degraded (pure-Python fallback) deployment is visible
  without log spelunking.

Consumers keep the lazy-build contract: nothing compiles at import
time; the first ``load()`` (via ``available()``) pays the build once
per source change.
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import shutil
import subprocess
import threading
import time
from typing import Dict, List, Optional, Sequence, Tuple

__all__ = [
    "NativeLib", "build_status", "compiler_candidates",
    "SANITIZER_FLAGS", "sanitizer_variant", "build_tool",
]

_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
_BUILD_DIR = os.path.join(_ROOT, "native", "build")

#: name -> NativeLib, for the /debug/stats surface
_REGISTRY: Dict[str, "NativeLib"] = {}


def compiler_candidates() -> List[str]:
    """Compilers to try, in order: $CXX (when set), g++, clang++."""
    out = []
    cxx = os.environ.get("CXX")
    if cxx:
        out.append(cxx)
    for cc in ("g++", "clang++"):
        if cc not in out:
            out.append(cc)
    return out


# ---------------------------------------------------------------------------
# Sanitizer-instrumented variants (ISSUE 9)
# ---------------------------------------------------------------------------

#: variant -> compile flags replacing the default -O2. -O1 keeps the
#: instrumented binaries debuggable AND fast enough for the race-hunt
#: drives; frame pointers keep the reports readable.
SANITIZER_FLAGS: Dict[str, List[str]] = {
    "tsan": ["-fsanitize=thread", "-O1", "-g", "-fno-omit-frame-pointer"],
    "asan": ["-fsanitize=address", "-O1", "-g", "-fno-omit-frame-pointer"],
    "ubsan": ["-fsanitize=undefined", "-fno-sanitize-recover=undefined",
              "-O1", "-g"],
}


def sanitizer_variant() -> Optional[str]:
    """The process-wide sanitizer variant from ``TPU_NATIVE_SANITIZE``
    (tsan/asan/ubsan; empty/unset/unknown -> None). Every NativeLib
    resolves this at first load, so an instrumented serving process is
    one env var away — and the variant lands in bench rows and
    build_status so instrumented runs are machine-distinguishable."""
    raw = os.environ.get("TPU_NATIVE_SANITIZE", "").strip().lower()
    return raw if raw in SANITIZER_FLAGS else None


def build_tool(
    name: str,
    sources: Sequence[str],
    extra_flags: Sequence[str] = (),
    variant: Optional[str] = None,
    timeout: float = 300.0,
) -> Tuple[Optional[str], Optional[str]]:
    """Build a native EXECUTABLE (the race-hunt drivers) with the same
    compiler search / content-stamp discipline as NativeLib. Returns
    (path, None) on success, (None, error) on failure — callers (the
    slow test suite) skip when the toolchain can't build the variant.

    ``sources[0]`` is the translation unit; the rest fold into the
    staleness digest (the drivers ``#include`` the library source)."""
    os.makedirs(_BUILD_DIR, exist_ok=True)
    suffix = f".{variant}" if variant else ""
    out_path = os.path.join(_BUILD_DIR, f"{name}{suffix}")
    stamp_path = out_path + ".sha256"
    abs_sources = [os.path.join(_ROOT, s) for s in sources]
    san_flags = SANITIZER_FLAGS.get(variant or "", [])
    flags = [*san_flags, *extra_flags] if san_flags else ["-O2", *extra_flags]
    try:
        h = hashlib.sha256()
        for path in abs_sources:
            with open(path, "rb") as f:
                h.update(f.read())
        h.update(" ".join(flags).encode())
        digest: Optional[str] = h.hexdigest()
    except OSError:
        digest = None
    if digest is not None and os.path.exists(out_path):
        try:
            with open(stamp_path) as f:
                if f.read().strip() == digest:
                    return out_path, None
        except OSError:
            pass
    attempts: List[str] = []
    for cxx in compiler_candidates():
        if shutil.which(cxx) is None:
            attempts.append(f"{cxx}: not found")
            continue
        cmd = [cxx, "-std=c++17", *flags, "-o", out_path, abs_sources[0]]
        try:
            proc = subprocess.run(
                cmd, capture_output=True, text=True, timeout=timeout,
            )
        except (OSError, subprocess.TimeoutExpired) as exc:
            attempts.append(f"{cxx}: invocation failed: {exc}")
            continue
        if proc.returncode != 0:
            attempts.append(f"{cxx}: {proc.stderr[-1500:]}")
            continue
        if digest is not None:
            with open(stamp_path, "w") as f:
                f.write(digest)
        return out_path, None
    return None, " | ".join(attempts) or "no compiler candidates"


class NativeLib:
    """One vendored shared library: sources + flags -> loaded CDLL.

    ``sources`` are paths relative to the repo root (the first entry is
    the translation unit handed to the compiler; the rest are headers
    folded into the staleness digest). ``extra_flags`` extend the common
    ``-O2 -std=c++17 -shared -fPIC`` set.
    """

    def __init__(
        self,
        name: str,
        sources: Sequence[str],
        extra_flags: Sequence[str] = (),
        timeout: float = 180.0,
    ):
        self.name = name
        self.sources = [os.path.join(_ROOT, s) for s in sources]
        self.extra_flags = list(extra_flags)
        self.timeout = timeout
        self.so_path = os.path.join(_BUILD_DIR, f"lib{name}.so")
        self.stamp_path = self.so_path + ".sha256"
        self._lock = threading.Lock()
        self._lib: Optional[ctypes.CDLL] = None
        self._build_error: Optional[str] = None
        #: sanitizer variant resolved at first load (TPU_NATIVE_SANITIZE);
        #: None = the plain -O2 build
        self.variant: Optional[str] = None
        #: wall seconds this process spent compiling the library; None
        #: when a current build was found on disk
        self.build_seconds: Optional[float] = None
        _REGISTRY[name] = self

    # -- staleness ----------------------------------------------------------

    def _digest(self) -> Optional[str]:
        try:
            h = hashlib.sha256()
            for path in self.sources:
                with open(path, "rb") as f:
                    h.update(f.read())
            h.update(" ".join(self._flags()).encode())
            return h.hexdigest()
        except OSError:
            return None

    def _flags(self) -> List[str]:
        """Per-variant compile flags: sanitizer flags replace the -O2
        default; a variant change reflows into the digest AND the
        output name, so instrumented and plain builds never clobber
        each other."""
        san = SANITIZER_FLAGS.get(self.variant or "", [])
        base = san if san else ["-O2"]
        return [*base, *self.extra_flags]

    def _stale(self, digest: Optional[str]) -> bool:
        if not os.path.exists(self.so_path):
            return True
        if digest is None:
            return False  # no source available; trust the existing binary
        try:
            with open(self.stamp_path) as f:
                return f.read().strip() != digest
        except OSError:
            return True

    # -- build --------------------------------------------------------------

    def _build(self, digest: Optional[str]) -> Optional[str]:
        os.makedirs(_BUILD_DIR, exist_ok=True)
        attempts: List[str] = []
        for cxx in compiler_candidates():
            if shutil.which(cxx) is None:
                attempts.append(f"{cxx}: not found")
                continue
            cmd = [
                cxx, "-std=c++17", "-shared", "-fPIC",
                *self._flags(), "-o", self.so_path, self.sources[0],
            ]
            try:
                proc = subprocess.run(
                    cmd, capture_output=True, text=True,
                    timeout=self.timeout,
                )
            except (OSError, subprocess.TimeoutExpired) as exc:
                attempts.append(f"{cxx}: invocation failed: {exc}")
                continue
            if proc.returncode != 0:
                attempts.append(f"{cxx}: {proc.stderr[-1500:]}")
                continue
            if digest is not None:
                with open(self.stamp_path, "w") as f:
                    f.write(digest)
            return None
        return " | ".join(attempts) or "no compiler candidates"

    # -- load ---------------------------------------------------------------

    def load(self) -> Optional[ctypes.CDLL]:
        """Build (when stale) and dlopen; memoized, thread-safe. Returns
        None on failure with the error kept in ``build_error``."""
        with self._lock:
            if self._lib is not None or self._build_error is not None:
                return self._lib
            self.variant = sanitizer_variant()
            if self.variant is not None:
                # sanitizer builds get their own artifact + stamp; note
                # that dlopen'ing a TSAN/ASAN .so into a plain python
                # needs the runtime preloaded (LD_PRELOAD=libtsan.so.0)
                # — the race-hunt suite uses standalone driver
                # executables instead (native/race_hunt_*.cc)
                self.so_path = os.path.join(
                    _BUILD_DIR, f"lib{self.name}.{self.variant}.so"
                )
                self.stamp_path = self.so_path + ".sha256"
            digest = self._digest()
            if self._stale(digest):
                started = time.monotonic()
                self._build_error = self._build(digest)
                self.build_seconds = round(time.monotonic() - started, 3)
                if self._build_error is not None:
                    return None
            try:
                self._lib = ctypes.CDLL(self.so_path)
            except OSError as exc:
                self._build_error = str(exc)
                return None
            return self._lib

    @property
    def build_error(self) -> Optional[str]:
        return self._build_error

    @property
    def loaded(self) -> bool:
        return self._lib is not None

    def peek(self) -> Optional[ctypes.CDLL]:
        """The loaded library WITHOUT triggering a build — for optional
        fast paths (e.g. the sharded partition assist) that must never
        stall a serving process on a first-use compile."""
        return self._lib


def build_status() -> dict:
    """Per-library load state for ``GET /debug/stats``: attempted
    libraries only (``load()`` not yet called -> ``attempted: false``,
    no build is triggered by reporting)."""
    out = {}
    for name, lib in sorted(_REGISTRY.items()):
        attempted = lib.loaded or lib.build_error is not None
        out[name] = {
            "attempted": attempted,
            "loaded": lib.loaded,
            "build_error": lib.build_error,
            "build_seconds": lib.build_seconds,
            "sanitizer": lib.variant,
        }
    return out
