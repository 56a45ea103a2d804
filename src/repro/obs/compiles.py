"""Process-wide count of JAX compilations.

A step that compiles inside a measured loop spends seconds where it
should spend milliseconds, and nothing in the loop's own numbers says
why.  This counter listens to the two events JAX records through
``jax.monitoring`` for every new program: the trace of a function to a
jaxpr (``/jax/core/compile/jaxpr_trace_duration``) and the backend
compile of its lowered module (``/jax/core/compile/backend_compile_duration``,
recorded on a persistent-cache hit too).  Each event keeps its kind, the
``fun_name`` JAX gives it and its wall-clock span, so a log can say which
function compiled and when.

Nothing listens until :func:`install` is called; importing this module
changes nothing.  ``install`` is idempotent.

    from repro.obs import compiles
    compiles.install()
    n0 = compiles.count()
    ...                                  # the loop
    new = compiles.events()[n0:]         # what compiled inside it
"""

from __future__ import annotations

import threading
from typing import List, NamedTuple

TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_KINDS = {TRACE_EVENT: "trace", COMPILE_EVENT: "compile"}


class CompileEvent(NamedTuple):
    kind: str          # "trace" or "compile"
    fun_name: str      # as JAX names it: "train_step", "jit(train_step)"
    start_s: float     # time.time() at the start and at the end
    end_s: float


_events: List[CompileEvent] = []
_lock = threading.Lock()
_installed = False


def _listen(event: str, start_s: float, end_s: float, **kw) -> None:
    kind = _KINDS.get(event)
    if kind is not None:
        _events.append(CompileEvent(kind, str(kw.get("fun_name", "")),
                                    start_s, end_s))


def install() -> None:
    """Start counting (once per process; later calls do nothing)."""
    global _installed
    with _lock:
        if _installed:
            return
        import jax
        jax.monitoring.register_event_time_span_listener(_listen)
        _installed = True


def installed() -> bool:
    return _installed


def count() -> int:
    """Traces plus backend compiles recorded so far."""
    return len(_events)


def events() -> List[CompileEvent]:
    """A copy of every event recorded so far, oldest first."""
    return list(_events)
