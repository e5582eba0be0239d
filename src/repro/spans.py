"""Host spans of the serving engine, recorded while a JAX profiler session runs.

To look inside the engine, start a profiler session (``jax.profiler.trace(dir)``
or ``jax.profiler.start_trace``) and drive ``ServeEngine``.  Each span is a
``jax.profiler.TraceAnnotation`` in the session's trace, with its args as event
stats, beside the device ops (TensorBoard, Perfetto).  The same spans are kept
in this process, stamped with ``time.perf_counter()``; ``take()`` hands them
over and starts a new buffer.  With no session running a span costs one check
and records nothing.

A span is ``Span(name, start, end, parent, args)``: ``parent`` is the index, in
the list ``take()`` returns, of the span it ran inside (-1: none, or dropped).
The buffer holds at most ``CAP`` spans; ``take()``'s ``dropped`` counts the
rest.  Whether a tick records is decided once, when ``engine.tick`` opens: its
children follow that decision.

Spans of ``ServeEngine`` (args in brackets; rids are space-separated):

- ``engine.tick`` (one per ``step()``), with the tick's counters: ``tick``,
  ``admitted``, ``decodable`` (slots in the decode batch), ``prefill_tokens``,
  ``decode_tokens``, ``prefill_launches`` (one-shot and chunk launches),
  ``pages_allocated``, ``cow_copies``, ``prefix_hit_pages``, ``bt_uploads``,
  ``preemptions``, ``finished``, ``retraced`` (jit traces during the tick).
  Its children:

  - ``engine.admit``: expiry, admission, pack planning, and in chunk mode
    page allocation and the chunk plan.
  - ``engine.prefill`` [bucket, k, tokens, rids]: one per one-shot launch,
    single or packed; ``engine.chunk`` [tokens, rids]: one per chunk launch.
  - ``engine.draft``: speculative drafting and its grant.
  - ``engine.pages``: page appends for the decode batch and copy-on-write.
  - ``engine.bt_upload``: the block table sent to the device (only when it
    changed); inside whichever phase needed it.
  - ``engine.decode``, ``engine.verify``: the decode (or speculative verify)
    launch.
  - ``engine.health``: an invariant sweep.

  Every launch span holds ``<launch>.wait`` (``engine.prefill.wait``,
  ``engine.chunk.wait``, ``engine.decode.wait``, ``engine.verify.wait``): the
  first read of the launch's outputs, where the host waits for the device.
  ``engine.decode.post`` and ``engine.verify.post`` are the token bookkeeping
  after it.

- ``request.queued`` [rid, prompt_len]: from ``submit()`` to the admission
  that gives the request a slot, kept when recording was on at ``submit()``.
  In the profiler's trace it is an event at admission carrying ``rid`` and
  ``wait_ms``.
"""

from __future__ import annotations

import time
from typing import Dict, List, NamedTuple, Optional

from jax.profiler import TraceAnnotation

__all__ = ["CAP", "Span", "Spans", "now", "record", "span", "take"]

CAP = 1 << 16  # spans kept between two take() calls (a 50 s chat window: ~6,000)


class Span(NamedTuple):
    name: str
    start: float  # time.perf_counter() seconds
    end: float
    parent: int  # index of the enclosing span in the taken list, -1 for none
    args: Dict[str, object]


class Spans(list):
    """The spans ``take()`` returns, and how many the cap dropped."""

    dropped = 0


class _State:
    """The process's buffer: a profiler session belongs to the whole process,
    so the spans it brackets do too."""

    def __init__(self):
        self.buf = Spans()
        self.open: List[int] = []  # buffer indices of recording spans now open
        self.off = 0  # depth of open spans that decided not to record


_S = _State()


class _Off:
    """The shared span that records nothing."""

    __slots__ = ()
    recording = False

    def __enter__(self):
        _S.off += 1
        return self

    def __exit__(self, *exc):
        _S.off -= 1

    def set(self, **args):
        pass


_OFF = _Off()


class _On:
    __slots__ = ("name", "args", "ann", "buf", "index", "parent", "start")
    recording = True

    def __init__(self, name, args):
        self.name, self.args = name, args

    def __enter__(self):
        self.ann = TraceAnnotation(self.name, **self.args)
        self.ann.__enter__()
        self.buf = _S.buf
        self.parent = _S.open[-1] if _S.open else -1
        if len(self.buf) < CAP:
            self.index = len(self.buf)
            self.buf.append(None)  # filled at exit: children point here
        else:
            self.index = -1
            self.buf.dropped += 1
        _S.open.append(self.index)
        self.start = time.perf_counter()
        return self

    def __exit__(self, *exc):
        end = time.perf_counter()
        _S.open.pop()
        if self.index >= 0:
            self.buf[self.index] = Span(self.name, self.start, end, self.parent, self.args)
        self.ann.__exit__(*exc)

    def set(self, **args):
        """Add args known only at the end (a tick's counters)."""
        self.args.update(args)
        self.ann.set_metadata(**args)


def span(name: str, **args):
    """Context manager for one span; ``.recording`` says whether it records
    and ``.set(**args)`` adds args before it closes."""
    if _S.off or not (_S.open or TraceAnnotation.is_enabled()):
        return _OFF
    return _On(name, args)


def now() -> Optional[float]:
    """The spans' clock while a profiler session runs, else None."""
    return time.perf_counter() if TraceAnnotation.is_enabled() else None


def record(name: str, start: float, **args) -> None:
    """Keep a span that began at ``start`` (from ``now()``) and ends now,
    whether or not a session still runs; in a running session it is an event
    here carrying ``args`` and ``wait_ms``."""
    end = time.perf_counter()
    if TraceAnnotation.is_enabled():
        with TraceAnnotation(name, wait_ms=1e3 * (end - start), **args):
            pass
    if len(_S.buf) < CAP:
        _S.buf.append(Span(name, start, end, -1, args))
    else:
        _S.buf.dropped += 1


def take() -> Spans:
    """The spans kept since the last call, in the order they opened
    (``request.queued``: when its request was admitted)."""
    out, _S.buf = _S.buf, Spans()
    return out
