"""Trace spans: one bounded in-memory buffer, and the per-process JSONL
event log that `FLAGS_obs_dir` drains it to.

A span is one timed unit of work. Every span of the process, whoever
timed it, lands in ONE buffer as

  (name, kind, sid, psid, t0, t1, tid, attrs)

with t0/t1 on `time.perf_counter()`: the clock of the serving
`Request` timestamps, so a span and a request's own times subtract
exactly. `spans()` returns the buffer's content as dicts. The buffer
holds the newest BUFFER_SPANS spans; an older one that falls out is
counted in the `trace.dropped` counter. It records exactly while the
telemetry registry is enabled (`telemetry.enable()`/`disable()`): there
is no switch of its own, and the disabled path is one boolean read.

Three ways in, one record shape:

  profiler.RecordEvent   the program's scoped span (executor, serving,
                         reader); it opens through begin()/end() here
  span()/server_span()   the cross-process scopes of the RPC layer: a
                         client span's sid rides the wire meta dict and
                         the server's handler span re-uses it
  record_span()          a span whose two ends were seen on different
                         threads, or that is known only afterwards (the
                         pipelined RPC client; a request's queue wait)

Parent ids come from a thread-local stack of open scopes: a span opened
inside another records that scope's sid as `psid`. annotate() adds
attributes to the innermost open scope from code that runs inside it.

With `FLAGS_obs_dir` set, enable() opens
`<obs_dir>/events-<role>-<pid>.jsonl` and flush() MOVES the buffer's
content into it (the telemetry exporter thread calls flush() every
`FLAGS_obs_flush_secs`, and it runs once more at exit): no file is
touched when a span ends. Times are converted to unix epoch seconds
with one (time.time(), perf_counter()) anchor per process, so
obs/report.py merges the logs of several processes as before. Three
record shapes share the file:

  span   {'type':'span','kind':'client'|'server'|'host'|..., 'name',
          'sid','psid', 't0','t1' (unix epoch seconds), 'tid','pid',
          'role', ...attrs}
  fault  {'type':'fault', 't', 'action', ...}      (trainer FaultEvents,
                                                    supervisor restarts)
  mark   {'type':'mark', 't', 'name', ...}         (one-shot milestones)

Instant records (event()) are rare and often the last thing a process
says, so they are written through at once, not buffered.

Propagation: the RPC clients stamp `meta['trace'] = {'sid': ...}` on
each outbound request — an OPTIONAL key in the schemaless JSON meta
dict, so there is no wire-version bump and an untraced (or older) peer
simply ignores it. report.py links a client span to its server handling
by the shared sid (flow events) and estimates per-role clock offsets
from request/reply midpoints.
"""
from __future__ import annotations

import atexit
import binascii
import collections
import contextlib
import itertools
import json
import os
import threading
import time

from . import telemetry

__all__ = ['BUFFER_SPANS', 'spans', 'begin', 'end', 'annotate', 'span',
           'server_span', 'record_span', 'event', 'wire_trace',
           'current_sid', 'new_id', 'enabled', 'enable', 'disable',
           'flush', 'clear']

# The buffer's bound. A 51 s serving window with its set-up and tail
# leaves about 30 thousand spans (PERF.md section 6), so this holds four
# such runs. Full, it keeps 37 to 57 MB: a span is 284 bytes with no
# attributes and 432 with four (tracemalloc; tests/test_spans.py holds
# the figure under 500).
BUFFER_SPANS = 1 << 17

_buf = collections.deque(maxlen=BUFFER_SPANS)
_dropped = telemetry.counter('trace.dropped')
_ids = itertools.count(1)
_tls = threading.local()
_lock = threading.Lock()            # the log writer's
_file = None
_role = ''
# perf_counter() + _EPOCH_OFFSET is the unix time of the same instant
_EPOCH_OFFSET = time.time() - time.perf_counter()


def new_id():
    """A span id unique across processes (it rides the wire)."""
    return binascii.hexlify(os.urandom(8)).decode()


def enabled():
    return telemetry._enabled


class _Span(object):
    """An open scope on its thread's stack."""
    __slots__ = ('name', 'kind', 'sid', 'psid', 't0', 'attrs')


def _stack():
    try:
        return _tls.stack
    except AttributeError:
        stack = _tls.stack = []
        return stack


def current_sid():
    stack = getattr(_tls, 'stack', None)
    return stack[-1].sid if stack else None


def _record(rec):
    if len(_buf) == BUFFER_SPANS:       # the append drops the oldest
        _dropped.inc()
    _buf.append(rec)


def begin(name, kind='host', sid=None, attrs=None):
    """Open a scope on this thread (the registry must be on: callers
    check enabled() first, which is their whole disabled path). An id
    the caller does not give is a process-local integer."""
    sp = _Span()
    sp.name, sp.kind, sp.attrs = name, kind, attrs
    sp.sid = next(_ids) if sid is None else sid
    stack = _stack()
    sp.psid = stack[-1].sid if stack else None
    stack.append(sp)
    sp.t0 = time.perf_counter()
    return sp


def end(sp):
    """Close the scope begin() returned: one buffer append."""
    t1 = time.perf_counter()
    stack = _stack()
    if stack and stack[-1] is sp:
        stack.pop()
    _record((sp.name, sp.kind, sp.sid, sp.psid, sp.t0, t1,
             threading.get_ident(), sp.attrs))


def annotate(**attrs):
    """Add attributes to the innermost scope open on this thread (the
    reader's pop tells the `host_op:read` span how long it waited)."""
    stack = getattr(_tls, 'stack', None)
    if stack:
        sp = stack[-1]
        if sp.attrs is None:
            sp.attrs = attrs
        else:
            sp.attrs.update(attrs)


@contextlib.contextmanager
def span(name, kind='host', sid=None, **attrs):
    """Timed scope -> one span record; yields the open scope (None
    when tracing is off, so callers can guard their own extra work).
    Its id is unique across processes: wire_trace() sends it."""
    if not telemetry._enabled:
        yield None
        return
    sp = begin(name, kind, sid or new_id(), attrs)
    try:
        yield sp
    finally:
        end(sp)


def wire_trace(sp):
    """The meta-dict trace field for an outbound request carrying this
    client span's id — None (field omitted, untraced) when tracing is
    off."""
    if sp is None:
        return None
    return {'sid': sp.sid}


@contextlib.contextmanager
def server_span(name, trace_meta, **attrs):
    """Server-side handler scope. Only records when BOTH this process
    traces and the request carried a trace field: the span re-uses the
    client's sid, which is the whole cross-process correlation."""
    if not telemetry._enabled or not isinstance(trace_meta, dict) \
            or 'sid' not in trace_meta:
        yield None
        return
    with span(name, kind='server', sid=str(trace_meta['sid']),
              **attrs) as sp:
        yield sp


def record_span(name, kind, sid, t0, t1, **attrs):
    """Record a span that no scope on one thread can time: its start
    and end were observed on DIFFERENT threads (the pipelined RPC
    client), or it is known only afterwards (a serving request's queue
    wait, recorded when the request ends). t0/t1 are perf_counter()
    readings. It has no parent."""
    if not telemetry._enabled:
        return
    _record((name, kind, sid, None, t0, t1, threading.get_ident(),
             attrs or None))


def _as_dict(rec):
    name, kind, sid, psid, t0, t1, tid, attrs = rec
    d = dict(attrs) if attrs else {}
    d.update(name=name, kind=kind, sid=sid, psid=psid, t0=t0, t1=t1,
             tid=tid)
    return d


def spans():
    """The buffer's content, oldest first, as dicts: name, kind, sid,
    psid, t0, t1 (perf_counter seconds), tid, and the span's attributes
    beside them."""
    return [_as_dict(rec) for rec in list(_buf)]


def clear():
    """Empty the buffer (a measurement that wants only its own spans)."""
    _buf.clear()


def event(etype, **fields):
    """Instant record ('fault', 'mark', ...), written through to the
    event log; dropped when there is none."""
    if _file is None:
        return
    rec = {'type': etype, 't': time.time()}
    rec.update(fields)
    _write([rec])


def _write(recs):
    pid = os.getpid()
    with _lock:
        f = _file
        if f is None:
            return
        for rec in recs:
            rec['role'] = _role
            rec['pid'] = pid
            f.write(json.dumps(rec) + '\n')
        f.flush()


def _log_id(sid, kind='host'):
    """Ids are process-local integers in the buffer; a merged log needs
    them apart: the pid goes in, and a serving request's id (the sid of
    its `kind='request'` spans) is kept apart from the scopes' own."""
    if not isinstance(sid, int):
        return sid
    return '%s%d.%d' % ('req' if kind == 'request' else 'sp',
                        os.getpid(), sid)


def flush():
    """Move the buffer's content into the event log. Without a log
    (no enable(obs_dir)) the buffer is left as it is."""
    if _file is None:
        return
    recs = []
    while True:
        try:
            rec = _buf.popleft()
        except IndexError:
            break
        d = _as_dict(rec)
        d.update(type='span', sid=_log_id(d['sid'], d['kind']),
                 psid=_log_id(d['psid']),
                 t0=d['t0'] + _EPOCH_OFFSET, t1=d['t1'] + _EPOCH_OFFSET,
                 tid=d['tid'] & 0xffff)
        recs.append(d)
    if recs:
        _write(recs)


def _default_role():
    from ..flags import get_flag
    return get_flag('obs_role', '') or ('pid%d' % os.getpid())


def enable(obs_dir, role=None):
    """Open (or retarget) the event log that flush() drains the buffer
    to. Idempotent. Whether spans are recorded at all is the telemetry
    registry's switch, not this one."""
    global _file, _role
    disable()
    os.makedirs(obs_dir, exist_ok=True)
    role = role or _default_role()
    path = os.path.join(obs_dir,
                        'events-%s-%d.jsonl' % (role, os.getpid()))
    with _lock:
        _file = open(path, 'a')
        _role = role


def disable():
    """Drain what is buffered and close the event log."""
    global _file
    flush()
    with _lock:
        f, _file = _file, None
    if f is not None:
        try:
            f.close()
        except OSError:
            pass


atexit.register(flush)


def _bootstrap_from_flags():
    from ..flags import get_flag
    obs_dir = get_flag('obs_dir', '')
    if obs_dir:
        enable(obs_dir)


_bootstrap_from_flags()
