"""The wall time of this package's own imports (counter ``import.seconds``).

``photon_ml_tpu/__init__.py`` starts the clock at its first statement and
stops it at its last: that holds jax and pallas, which ``ops`` imports
eagerly. A module of the package first imported LATER (``game`` or
``config`` from a driver's set-up) is timed around its loader's
``exec_module`` by a finder on ``sys.meta_path``. Only the outermost import
of a thread counts: what a timed import pulls in, the package's own modules
included, is inside its time and is not counted again (the finder steps
aside while one runs).

Standard library only: this runs before anything else of the package can
be imported. The total lives here, not in the metrics registry, so
``telemetry.reset()`` cannot erase it; ``telemetry`` publishes it as the
counter ``import.seconds``."""

from __future__ import annotations

import sys
import threading
import time

_PREFIX = __name__.rpartition(".")[0] + "."
_lock = threading.Lock()
_local = threading.local()
_seconds = 0.0


def seconds() -> float:
    """Seconds of the package's outermost imports so far, this process."""
    return _seconds


def enter() -> bool:
    """One import of the package's begins on this thread; True where it is
    the outermost one (the one that counts)."""
    depth = getattr(_local, "depth", 0)
    _local.depth = depth + 1
    return depth == 0


def leave(outermost: bool, t0: float) -> None:
    """The import :func:`enter` began at ``time.perf_counter()`` = ``t0``
    has ended."""
    global _seconds
    _local.depth -= 1
    if outermost:
        dt = time.perf_counter() - t0
        with _lock:
            _seconds += dt


class _TimedLoader:
    """A module's own loader, its ``exec_module`` timed."""

    def __init__(self, loader):
        self._loader = loader

    def create_module(self, spec):
        return self._loader.create_module(spec)

    def exec_module(self, module) -> None:
        t0 = time.perf_counter()
        outermost = enter()
        try:
            self._loader.exec_module(module)
        finally:
            leave(outermost, t0)

    def __getattr__(self, name):  # get_source, get_resource_reader, ...
        return getattr(self._loader, name)


class _Finder:
    """Finds nothing itself: for a module of the package imported outside
    any timed import, it asks the finders after it and times the loader
    they return."""

    def find_spec(self, name, path=None, target=None):
        if not name.startswith(_PREFIX) or getattr(_local, "depth", 0):
            return None
        for finder in sys.meta_path:
            find = getattr(finder, "find_spec", None)
            if isinstance(finder, _Finder) or find is None:
                continue
            spec = find(name, path, target)
            if spec is not None:
                break
        else:
            return None
        if spec.loader is not None and hasattr(spec.loader, "exec_module"):
            spec.loader = _TimedLoader(spec.loader)
        return spec


if not any(isinstance(f, _Finder) for f in sys.meta_path):
    sys.meta_path.insert(0, _Finder())
