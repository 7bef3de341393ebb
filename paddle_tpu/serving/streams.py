"""One writer thread for every NDJSON token stream of the process.

A streamed ``/generate`` (or ``/adopt``) used to be written by its own
HTTP handler thread, woken through a queue once a token: a pass that
booked 64 tokens woke 64 threads, and the generation scheduler queued
for the interpreter behind all of them (6 ms a pass with 64 streams,
PERF.md section 6, PR 36).  Now the handler sends the status line and
headers, hands its socket to :data:`stream_writer` and sleeps until the
stream has ended.

* What the engine gets as ``on_token`` is :meth:`TokenStream.push`: a
  ``deque.append``, no lock, nobody woken.
* Where a booking batch ends (a settled decode step, a block commit, a
  prefill's first token, a speculative round, an adoption's replay) the
  scheduler calls :meth:`StreamWriter.flush`: one byte down a pipe if
  anything was pushed, so the writer wakes once a batch however many
  streams it fed.  Nothing else wakes it for a token, and it keeps no
  line back: a line leaves in the pass that booked it.
* The writer encodes the lines (``{"i": n, "token": t}``, byte for byte
  what ``json.dumps`` gives) and sends them on non-blocking sockets.
  What a socket does not take stays in that stream's own backlog until
  ``selectors`` says it is writable: a slow client delays no other
  stream and never the scheduler.  A send error marks the stream
  ``client_gone``; the sequence keeps generating and nothing more is
  written.
* The handler, woken by the request's future, encodes the summary line
  and gives it to the writer (:meth:`StreamWriter.finish`), which sends
  it after every token line and then sets the stream's ``done``.

The writer feeds :data:`stream_meter` with its own thread CPU seconds
and its seconds inside ``send``; the scheduler writes the differences on
``generation/iteration`` (``stream_cpu_ms``, ``stream_write_ms``).
Counters ``serving_stream_writer_wakeups`` / ``_lines`` / ``_sends`` /
``_would_block`` and the gauge ``serving_streams_open`` say how the
mechanism engages: lines a wake-up is about the live streams of a pass.
"""
from __future__ import annotations

import collections
import logging
import os
import selectors
import threading
import time
from typing import Optional

from .. import blackbox, telemetry
from ..monitor import stat_add

__all__ = ["StreamMeter", "StreamWriter", "TokenStream", "stream_meter",
           "stream_writer"]

logger = logging.getLogger("paddle_tpu.serving.streams")


class StreamMeter:
    """What writing the token streams takes, process-wide: seconds
    inside ``send`` and thread CPU seconds of the stream writer (a span
    a token would push a window's spans out of the ring).  The writer
    adds its share once a wake-up.  The generation scheduler reads both
    sums at the end of a pass and writes the differences on its
    ``generation/iteration`` span (``stream_write_ms``,
    ``stream_cpu_ms``): the writer shares one interpreter with it."""

    def __init__(self):
        self._lock = threading.Lock()
        self._write_s = 0.0
        self._cpu_s = 0.0

    def add(self, write_s: float, cpu_s: float):
        with self._lock:
            self._write_s += write_s
            self._cpu_s += cpu_s

    def totals(self):
        """``(write seconds, CPU seconds)`` so far."""
        with self._lock:
            return self._write_s, self._cpu_s


stream_meter = StreamMeter()

# what a handler puts into the pending batch beside token ids (a summary
# line travels as its bytes)
_START = object()       # the headers are out: the socket is the writer's
_DROP = object()        # the handler gave up: write nothing more


class TokenStream:
    """One streaming connection.  ``push`` is the ``on_token`` the engine
    is given (called on the scheduler thread only); every other field
    but ``pushed`` belongs to the writer thread until ``done`` is set."""

    __slots__ = ("sock", "pushed", "client_gone", "done", "_pending",
                 "_lines", "_out", "_live", "_closed", "_waiting",
                 "_ended", "_registered")

    def __init__(self, sock, pending):
        self.sock = sock
        self.pushed = 0             # tokens the engine has booked to it
        self.client_gone = False
        self.done = threading.Event()
        self._pending = pending
        self._lines = 0             # token lines encoded
        self._out = bytearray()     # encoded and not yet taken
        self._live = False          # headers out, socket non-blocking
        self._closed = False        # summary queued or dropped
        self._waiting = False       # registered for writability
        self._ended = False
        self._registered = False

    def push(self, tok, ts):
        self.pushed += 1
        self._pending.append((self, tok))


class StreamWriter:
    """The thread that writes every token stream (module docstring).
    It lives while a server holds it (:meth:`acquire` / :meth:`release`)
    or a stream is open, and is started by the first :meth:`open`."""

    def __init__(self, meter: StreamMeter):
        self._meter = meter
        self._pending: collections.deque = collections.deque()
        self._lock = threading.Lock()       # thread lifetime, _refs, _open
        self._thread: Optional[threading.Thread] = None
        self._refs = 0
        self._open = 0
        # the wake-up pipe lives as long as the process: a flush racing a
        # thread's exit must never write into a descriptor number that a
        # client's socket has since been given
        self._wake_r: Optional[int] = None
        self._wake_w: Optional[int] = None
        self._signalled = False
        self._sel: Optional[selectors.BaseSelector] = None
        self._ended: list = []
        self._metered = False
        self._send_s = 0.0
        self.wakeups = 0
        self.lines = 0
        self.sends = 0
        self.would_block = 0

    # -- the servers' side ---------------------------------------------------
    def acquire(self):
        """A server that may stream holds the writer while it listens."""
        with self._lock:
            self._refs += 1

    def release(self):
        with self._lock:
            self._refs -= 1
            idle = self._refs == 0
        if idle:
            self._signal()      # the thread ends with the last stream

    # -- a handler's side ----------------------------------------------------
    def open(self, sock) -> TokenStream:
        """A stream over ``sock``.  Its ``push`` may be called from now
        on; nothing is written before :meth:`register`."""
        with self._lock:
            if self._wake_r is None:
                self._wake_r, self._wake_w = os.pipe()
                os.set_blocking(self._wake_r, False)
                os.set_blocking(self._wake_w, False)
            self._open += 1
            n = self._open
            if self._thread is None:
                self._thread = threading.Thread(
                    target=self._run, name="serving-stream-writer",
                    daemon=True)
                self._thread.start()
        telemetry.gauge_set("serving_streams_open", n)
        return TokenStream(sock, self._pending)

    def register(self, stream: TokenStream):
        """The status line and headers are out: the socket is the
        writer's until ``stream.done``."""
        stream.sock.setblocking(False)
        stream._registered = True
        self._pending.append((stream, _START))
        self._signal()

    def finish(self, stream: TokenStream, summary: bytes):
        """Send ``summary`` after every token line pushed so far, then
        set ``stream.done``.  Tokens pushed later are dropped."""
        self._pending.append((stream, summary))
        self._signal()

    def close(self, stream: TokenStream, timeout: float = 5.0):
        """The handler takes its socket back.  A stream the writer still
        holds (its summary never left) is dropped first."""
        if stream._registered and not stream.done.is_set():
            self._pending.append((stream, _DROP))
            self._signal()
            if not stream.done.wait(timeout):
                logger.warning("the stream writer did not let go of a "
                               "stream within %.1f s", timeout)
        with self._lock:
            self._open -= 1
            n = self._open
            idle = self._refs == 0
        telemetry.gauge_set("serving_streams_open", n)
        if idle:
            self._signal()

    # -- the scheduler's side ------------------------------------------------
    def flush(self):
        """A booking batch ended: wake the writer if it was pushed to."""
        if self._pending and not self._signalled:
            self._signal()

    def stats(self) -> dict:
        with self._lock:
            n = self._open
        return {"open": n, "wakeups": self.wakeups, "lines": self.lines,
                "sends": self.sends, "would_block": self.would_block}

    def _signal(self):
        # gc-ok: lock-bare-access written once, before any stream exists
        if self._wake_w is None:
            return              # no stream was ever opened
        # set before the write: the writer clears it before it drains, so
        # whoever sees it set has pushed before that drain
        self._signalled = True
        try:
            os.write(self._wake_w, b"\0")
        except BlockingIOError:
            pass  # ok: the pipe is full of wake-ups already

    # -- the writer thread ---------------------------------------------------
    def _run(self):
        sel = self._sel = selectors.DefaultSelector()
        # gc-ok: lock-bare-access written once, before this thread starts
        sel.register(self._wake_r, selectors.EVENT_READ)
        cpu0 = time.thread_time()
        try:
            while True:
                for key, _ in sel.select():
                    if key.data is None:
                        try:
                            os.read(self._wake_r, 4096)
                        except BlockingIOError:
                            pass  # ok: nothing left to read
                    else:               # writable again (rare)
                        w0 = time.perf_counter()
                        self._send(key.data)
                        self._send_s += time.perf_counter() - w0
                self._signalled = False
                # (metered only with telemetry on: no clock is read
                # without it)
                self._metered = telemetry.enabled()
                lines, sends, blocked = \
                    self.lines, self.sends, self.would_block
                if self._pending:
                    self.wakeups += 1
                    stat_add("serving_stream_writer_wakeups")
                    self._drain()
                if self.lines != lines:
                    stat_add("serving_stream_writer_lines",
                             self.lines - lines)
                if self.sends != sends:
                    stat_add("serving_stream_writer_sends",
                             self.sends - sends)
                if self.would_block != blocked:
                    stat_add("serving_stream_writer_would_block",
                             self.would_block - blocked)
                if self._metered:
                    cpu1 = time.thread_time()
                    if cpu0 is not None:
                        self._meter.add(self._send_s, cpu1 - cpu0)
                    cpu0, self._send_s = cpu1, 0.0
                else:
                    cpu0 = None
                # after the meter: whoever saw a stream end finds its
                # cost booked
                for stream in self._ended:
                    stream.done.set()
                self._ended.clear()
                with self._lock:
                    if not (self._refs or self._open or self._pending):
                        self._thread = None
                        return
        except BaseException as e:
            # every open stream stalls with this thread (its handler
            # gives up at its wait budget): leave the flight recorder
            blackbox.dump_exception("stream_writer", e)
            raise
        finally:
            sel.close()
            with self._lock:
                if self._thread is threading.current_thread():
                    self._thread = None     # the next open() starts anew

    def _drain(self):
        """Take the pending batch: encode each stream's lines behind
        what it still owes, then send once a stream."""
        pending = self._pending
        touched = {}
        while True:
            try:
                stream, item = pending.popleft()
            except IndexError:
                break
            if stream._ended:
                continue
            if item is _START:
                stream._live = True
            elif item is _DROP:
                stream.client_gone = True
                self._end(stream)
                continue
            elif stream._closed:
                continue        # booked after the summary: a timed-out wait
            elif item.__class__ is bytes:
                stream._closed = True
                if stream.client_gone:
                    self._end(stream)
                    continue
                stream._out += item
            elif stream.client_gone:
                continue        # the sequence keeps generating
            else:
                stream._lines += 1
                self.lines += 1
                stream._out += b'{"i": %d, "token": %d}\n' % (
                    stream._lines, item)
            touched[stream] = None
        # one clock reading a batch, not two a send: the sends are all
        # this loop does
        w0 = time.perf_counter() if self._metered else 0.0
        for stream in touched:
            # (a stream waiting for its socket is sent from select())
            if stream._live and not stream._waiting and not stream._ended:
                self._send(stream)
        if self._metered:
            self._send_s += time.perf_counter() - w0

    def _send(self, stream: TokenStream):
        out = stream._out
        if out:
            try:
                n = stream.sock.send(out)
            except BlockingIOError:
                n = 0
            except OSError:
                # the client hung up mid-stream: the sequence keeps
                # generating (no cancellation), we just stop writing
                stream.client_gone = True
                n = len(out)
            self.sends += 1
            del out[:n]
        if out:
            self.would_block += 1
            if not stream._waiting:
                stream._waiting = True
                self._sel.register(stream.sock, selectors.EVENT_WRITE,
                                   stream)
            return
        self._unwatch(stream)
        if stream._closed:
            self._end(stream)       # the summary has left

    def _unwatch(self, stream: TokenStream):
        if stream._waiting:
            stream._waiting = False
            try:
                self._sel.unregister(stream.sock)
            except (KeyError, ValueError, OSError) as e:
                # a handler that gave up on this thread closed it
                logger.debug("stream socket already gone: %s", e)

    def _end(self, stream: TokenStream):
        """Nothing more is written to ``stream``; its ``done`` is set at
        the end of this wake-up."""
        self._unwatch(stream)
        stream._out.clear()
        stream._closed = stream._ended = True
        self._ended.append(stream)


stream_writer = StreamWriter(stream_meter)
