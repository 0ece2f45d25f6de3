"""One control for the device profiler and the program's spans in its trace.

``start(log_dir)`` / ``stop()`` wrap ``jax.profiler`` in the process that
holds the chip; ``span(name, **attrs)`` puts a host span into the profiler's
own trace (``jax.profiler.TraceAnnotation``: same file, same clock as the
device plane), so an idle gap on the device can be named by what the host did
in it. While no profile runs, ``span`` returns one shared no-op object: a flag
test, nothing built. The debug endpoints ``POST /v1/debug/profile/start|stop``
(engine API server, behind ``--enable-debug-endpoints``) call this module, so
the spans switch with the profiler; a profile started through ``jax.profiler``
directly (as the benchmark's engine child does) holds the device plane and the
named programs, not the spans. See docs/tracing.md for the span names and
their attributes.

The Python tracer is off (``python_tracer_level = 0``): with it every Python
call of every server thread lands in the trace, which slows the threads that
are traced (a 1.5 s loop took 25.8 s under it in PR 25's probe on the v5e) and
makes a trace of hundreds of MB. The program's spans are TraceMe events and
need only the host tracer.
"""

from __future__ import annotations

import os
import threading
import time

_lock = threading.Lock()
_active = False
_log_dir = ""


class _NoSpan:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set_metadata(self, **attrs):
        """What a TraceAnnotation takes once it is open; nothing here."""


_NO_SPAN = _NoSpan()


def active() -> bool:
    return _active


def span(name: str, **attrs):
    """A context manager: a TraceAnnotation while a profile runs, else the
    shared no-op. Attribute values must be str, int or float; what is known
    only once the span is open goes in through its ``set_metadata(**attrs)``."""
    if not _active:
        return _NO_SPAN
    import jax

    return jax.profiler.TraceAnnotation(name, **attrs)


def start(log_dir: str) -> None:
    """Start the profiler, writing under ``log_dir``. Raises RuntimeError when
    one is already running in this process."""
    global _active, _log_dir
    import jax

    with _lock:
        if _active:
            raise RuntimeError(f"a profile is already running (into {_log_dir})")
        os.makedirs(log_dir, exist_ok=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(log_dir, profiler_options=options)
        _log_dir, _active = log_dir, True


def stop() -> dict:
    """Stop the profiler and write the trace. Returns {"stop_s", "path"}:
    the seconds the stop took and the directory given to ``start``."""
    global _active
    import jax

    with _lock:
        if not _active:
            raise RuntimeError("no profile is running")
        # off first: a span opened while the trace is written belongs to no trace
        _active = False
        t0 = time.perf_counter()
        jax.profiler.stop_trace()
        return {"stop_s": time.perf_counter() - t0, "path": _log_dir}
