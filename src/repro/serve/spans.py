"""Host spans of the serve path.

The serve path marks its steps with :func:`span`: ``serve.iteration``
(one engine loop pass), ``serve.prefill`` (the padded prefill's dispatch),
``serve.graft`` (admission's scatter on the device; a migration's graft
through the host adds ``fetch``/``merge``/``place`` parts), and
``serve.decode.wait`` (the host waiting for a decode step).  Where the
spans go is the caller's choice: :meth:`repro.serve.engine.ServeEngine.run`
activates its executor's ``spans`` attribute, any object with
``span(name, info)`` (:class:`Recorder`), for the length of the run.  With
no recorder active every span is one shared null context, so the off state
costs a call per span.

``info`` is kept by reference until the span ends, so a span may pass a
dict and fill it in from inside (the host graft's byte counts are known
only once the merged cache exists).
"""

from __future__ import annotations

import contextvars
from contextlib import contextmanager, nullcontext
from typing import ContextManager, Protocol

_NULL = nullcontext()


class Recorder(Protocol):
    def span(self, name: str, info=None) -> ContextManager: ...


class NullRecorder:
    """Records nothing."""

    def span(self, name: str, info=None) -> ContextManager:
        return _NULL


NULL = NullRecorder()
_active: contextvars.ContextVar = contextvars.ContextVar(
    "repro_serve_spans", default=NULL)


def span(name: str, info=None) -> ContextManager:
    """A span of the active recorder."""
    return _active.get().span(name, info)


@contextmanager
def recording(recorder: Recorder | None):
    """Make ``recorder`` (None: no recorder) the active one inside."""
    token = _active.set(NULL if recorder is None else recorder)
    try:
        yield
    finally:
        _active.reset(token)
