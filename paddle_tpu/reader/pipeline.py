"""Async input pipeline runtime: blocking queue + double-buffered device
prefetch.

Capability analog of the reference reader stack — LoDTensorBlockingQueue
(operators/reader/lod_tensor_blocking_queue.h), create_py_reader_op, and
create_double_buffer_reader_op (async prefetch to device) — rebuilt for
the TPU execution model:

- a feeder thread runs the user's Python generator and pushes host
  batches into a bounded queue (the blocking queue);
- with double buffering, a placer thread pops host batches and
  `jax.device_put`s them AHEAD of consumption into a small device-side
  queue, so the training step receives arrays already resident in HBM —
  the per-step host cost is a queue pop, and the host->device copy
  overlaps the previous step's compute.

The `read` host op (ops/io_ops.py) pops from the front queue each step
and raises core.EOFException when the pass ends (reference
reader EOF contract: users catch, reset, and start the next pass).
"""
from __future__ import annotations

import queue
import sys
import threading
import time

import numpy as np

from ..obs import telemetry as _tm
from ..obs import trace as _trace

__all__ = ['PyReader', 'get_reader', 'EOFException', 'leaked_threads']

# observability gauges mirroring this module's state: the leak count
# (also kept as the `_leaked` module counter for leaked_threads()) and
# the feed-queue depths sampled at every read() — a persistently empty
# host queue means the data source is the bottleneck
_LEAKED_GAUGE = _tm.gauge('reader.leaked_workers')
_HOST_DEPTH = _tm.gauge('reader.host_queue_depth')
_DEV_DEPTH = _tm.gauge('reader.device_queue_depth')

# Worker threads that outlived their join timeout (a feeder blocked
# inside a user generator cannot be interrupted from Python). They are
# daemons holding dead queues, so they are harmless to the NEXT pass —
# but each one pins the generator's frame (open files, sockets) until
# it unblocks, so leaks deserve a loud trail, not silence.
_leaked = 0
_leak_lock = threading.Lock()


def leaked_threads():
    """Process-wide count of reader worker threads that missed their
    join deadline (monotonic; see PyReader.join_timeout)."""
    return _leaked


def _note_leak(reader_name, thread):
    global _leaked
    with _leak_lock:
        _leaked += 1
        n = _leaked
    _LEAKED_GAUGE.set(n)
    sys.stderr.write(
        'WARNING: py_reader %r worker %s did not exit within its join '
        'timeout and was leaked (likely blocked in the user data '
        'generator); it holds the generator frame until it unblocks '
        '(%d leaked so far this process)\n'
        % (reader_name, thread.name, n))


class EOFException(Exception):
    """End of one data pass (reference fluid.core.EOFException)."""


_EOF = object()


class _SourceError(object):
    """Sentinel carrying a generator exception to the consuming step."""
    def __init__(self, exc):
        self.exc = exc


_readers = {}


def get_reader(name):
    r = _readers.get(name)
    if r is None:
        raise KeyError('py_reader %r is not registered' % name)
    return r


def stack_samples(batch, dtypes):
    """Stack a list of per-sample slot tuples into one array per slot
    (the paddle.batch convention) — shared by decorate_paddle_reader and
    the file-reader layers in layers/io.py."""
    slots = list(zip(*batch))
    return [np.stack([np.asarray(s, dtype=dt) for s in slot])
            for slot, dt in zip(slots, dtypes)]


class PyReader(object):
    """Runtime half of fluid.layers.py_reader. Also quacks enough like a
    Variable (name attr) for fluid.layers.read_file(reader)."""

    def __init__(self, name, shapes, dtypes, lod_levels=None, capacity=64,
                 use_double_buffer=True, device=None, join_timeout=10.0):
        self.name = name
        self.join_timeout = float(join_timeout)
        self.shapes = [tuple(s) for s in shapes]
        self.dtypes = list(dtypes)
        self.lod_levels = list(lod_levels or [0] * len(shapes))
        self.capacity = int(capacity)
        self.use_double_buffer = use_double_buffer
        self.device = device
        self._source = None
        self._host_q = None
        self._dev_q = None
        self._threads = []
        self._started = False
        self._stop = threading.Event()
        old = _readers.get(name)
        if old is not None and old._started:
            raise ValueError(
                'py_reader %r already exists and is started — reset() it '
                'before building another reader with the same name' % name)
        _readers[name] = self

    # -- decoration (reference py_reader decorate_* methods) ---------------
    def decorate_paddle_reader(self, reader):
        """reader(): generator of BATCHES, each a list of per-sample
        tuples (the paddle.batch convention); samples are stacked into
        one array per slot."""
        def source():
            for batch in reader():
                yield stack_samples(batch, self.dtypes)
        self._source = source
        return self

    def decorate_tensor_provider(self, provider):
        """provider(): generator of ready per-slot array lists. Slots that
        are already jax.Arrays pass through untouched (a provider may
        yield pre-placed device batches; the placer's device_put is then
        a no-op)."""
        def source():
            import jax
            for batch in provider():
                yield [a if isinstance(a, jax.Array)
                       else np.asarray(a, dtype=dt)
                       for a, dt in zip(batch, self.dtypes)]
        self._source = source
        return self

    decorate_batch_generator = decorate_tensor_provider

    # -- lifecycle ---------------------------------------------------------
    def start(self):
        if self._source is None:
            raise RuntimeError('py_reader %r: call decorate_paddle_reader '
                               'or decorate_tensor_provider first'
                               % self.name)
        if self._started:
            raise RuntimeError('py_reader %r already started (reset() '
                               'after EOFException)' % self.name)
        # threads capture THEIR pass's queues AND stop event as
        # arguments: a stale thread from a timed-out mid-pass reset
        # (blocked inside the user generator) can only ever touch its
        # own dead queues, and its own stop event stays set so it exits
        # instead of busy-polling for the lifetime of the next pass
        self._stop = threading.Event()
        self._host_q = queue.Queue(maxsize=self.capacity)
        self._threads = [threading.Thread(target=self._feed_loop,
                                          args=(self._host_q, self._stop),
                                          daemon=True)]
        if self.use_double_buffer:
            # depth 2: one batch in flight to device, one ready
            self._dev_q = queue.Queue(maxsize=2)
            self._threads.append(threading.Thread(
                target=self._place_loop,
                args=(self._host_q, self._dev_q, self._stop),
                daemon=True))
        for t in self._threads:
            t.start()
        self._started = True

    def reset(self):
        """Drain after EOF (or mid-pass) so start() can begin a new pass."""
        self._stop.set()
        for q in (self._host_q, self._dev_q):
            while q is not None:
                try:
                    q.get_nowait()
                except queue.Empty:
                    break
        for t in self._threads:
            t.join(timeout=self.join_timeout)
            if t.is_alive():
                _note_leak(self.name, t)
        self._threads = []
        self._started = False

    # -- step-side ---------------------------------------------------------
    def read(self):
        """One batch of per-slot values; raises EOFException at pass end.
        Double-buffered: values are jax.Arrays already on device."""
        if not self._started:
            raise RuntimeError('py_reader %r: start() before running the '
                               'program' % self.name)
        if self._host_q is not None:
            _HOST_DEPTH.set(self._host_q.qsize())
        if self._dev_q is not None:
            _DEV_DEPTH.set(self._dev_q.qsize())
        q = self._dev_q if self.use_double_buffer else self._host_q
        if _tm._enabled:
            # the wait goes onto the span the pop runs in: the executor's
            # `host_op:read`
            t0 = time.perf_counter()
            item = q.get()
            _trace.annotate(waited_ms=1e3 * (time.perf_counter() - t0))
        else:
            item = q.get()
        if isinstance(item, _SourceError):
            self._started = False
            raise RuntimeError('py_reader %r data source failed'
                               % self.name) from item.exc
        if item is _EOF:
            self._started = False
            for t in self._threads:
                t.join(timeout=self.join_timeout)
                if t.is_alive():
                    _note_leak(self.name, t)
            self._threads = []
            raise EOFException('pass end in py_reader %r' % self.name)
        return item

    # -- threads -----------------------------------------------------------
    def _feed_loop(self, host_q, stop):
        # a generator failure must surface at the consuming step, NOT
        # masquerade as a clean pass end (silent data truncation)
        tail = _EOF
        try:
            for batch in self._source():
                if stop.is_set():
                    return
                self._put_interruptible(host_q, batch, stop)
        except Exception as e:         # noqa: BLE001 — re-raised in read()
            tail = _SourceError(e)
        finally:
            self._put_interruptible(host_q, tail, stop)

    def _place_loop(self, host_q, dev_q, stop):
        import jax
        dev = self.device or jax.devices()[0]
        while True:
            # poll with a timeout so a mid-pass reset() (stop set while
            # the feeder is blocked elsewhere) cannot strand this thread
            if stop.is_set():
                return
            try:
                item = host_q.get(timeout=0.2)
            except queue.Empty:
                continue
            if item is _EOF or isinstance(item, _SourceError):
                self._put_interruptible(dev_q, item, stop)
                return
            try:
                placed = [jax.device_put(a, dev) for a in item]
            except Exception as e:     # noqa: BLE001 — re-raised in read()
                # a placement failure (bad dtype, device OOM) must reach
                # the consuming step, not kill this thread and hang read()
                self._put_interruptible(dev_q, _SourceError(e), stop)
                return
            self._put_interruptible(dev_q, placed, stop)

    def _put_interruptible(self, q, item, stop):
        while not stop.is_set():
            try:
                q.put(item, timeout=0.2)
                return
            except queue.Full:
                continue
