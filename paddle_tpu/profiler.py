"""Profiler: host RecordEvent scopes + device trace via jax.profiler, with a
chrome://tracing JSON export (reference paddle/fluid/platform/profiler.cc,
device_tracer.cc, tools/timeline.py, python/paddle/fluid/profiler.py:221).

The reference correlates CUPTI kernel records with per-op annotations; here
device-side timing comes from XLA/jax.profiler (xplane) and the host-side
RecordEvent table covers the executor segments, preserving the
profiler("All", "total", path) user contract.
"""
from __future__ import annotations

import contextlib
import json
import os
import threading
import time

from jax.profiler import TraceAnnotation as _TraceAnnotation

from .obs import telemetry as _telemetry
from .obs import trace as _obs_trace

# whether JAX's profiler is capturing: the annotation's own flag
_profile_running = _TraceAnnotation.is_enabled

__all__ = ['RecordEvent', 'record_event', 'profiler', 'start_profiler',
           'stop_profiler', 'reset_profiler', 'cuda_profiler']

_lock = threading.Lock()
_enabled = False
_events = []     # (name, thread_id, start_s, end_s)


class RecordEvent(object):
    """The program's one scoped span (reference platform/profiler.h
    RecordEvent): `with RecordEvent('exe.run', n_feeds=3) as ev:`.
    One scope, three readers, each with a switch that is already there:

    - while JAX's profiler captures a trace, the scope is a
      `jax.profiler.TraceAnnotation('pt.' + name)`: the span lands in
      the xplane's host plane, on the DEVICE TRACE'S clock, beside the
      device's ops (otherwise one flag check);
    - while the telemetry registry is on, the scope is recorded in
      obs/trace.py's buffer on `perf_counter()`, with its parent (the
      scope it was opened in, on this thread) and its attributes
      (otherwise one boolean read). `FLAGS_obs_dir` drains that buffer
      to the merged cluster timeline's event log;
    - between start_profiler() and stop_profiler() it also lands in the
      Fluid-style profiling report and chrome trace.

    `ev.attrs` may be filled in until the scope ends."""
    __slots__ = ('name', 'kind', 'attrs', 'start', '_ann', '_span')

    def __init__(self, name, kind='host', **attrs):
        self.name = name
        self.kind = kind
        self.attrs = attrs
        self.start = None
        self._ann = None
        self._span = None

    def __enter__(self):
        if _profile_running():
            self._ann = _TraceAnnotation('pt.' + self.name)
            self._ann.__enter__()
        if _telemetry._enabled:
            self._span = _obs_trace.begin(self.name, self.kind,
                                          attrs=self.attrs)
        if _enabled:
            self.start = time.perf_counter()
        return self

    def __exit__(self, *exc):
        if self.start is not None:
            end = time.perf_counter()
            # snapshot the enabled flag UNDER the lock, atomically with
            # the append: a concurrent reset_profiler()/stop_profiler()
            # otherwise races the unsynchronized read — the event could
            # land in a list the reset already replaced (or after a
            # stop), corrupting the next session's table
            with _lock:
                if _enabled:
                    _events.append((self.name, threading.get_ident(),
                                    self.start, end))
        if self._span is not None:
            _obs_trace.end(self._span)
        if self._ann is not None:
            self._ann.__exit__(*exc)
        return False


record_event = RecordEvent


def reset_profiler():
    global _events
    with _lock:
        _events = []


def start_profiler(state='All'):
    """state in {CPU, GPU, All} kept for API parity; device tracing is
    delegated to jax.profiler when a trace dir is given at stop time."""
    global _enabled
    if state not in ('CPU', 'GPU', 'All'):
        raise ValueError("state must be 'CPU', 'GPU' or 'All'")
    reset_profiler()
    with _lock:
        _enabled = True


def stop_profiler(sorted_key=None, profile_path='/tmp/profile'):
    global _enabled
    with _lock:
        _enabled = False
    _print_summary(sorted_key)
    if profile_path:
        _write_chrome_trace(profile_path)


def _aggregate():
    agg = {}
    with _lock:
        for name, tid, start, end in _events:
            total, calls, mn, mx = agg.get(name, (0.0, 0, float('inf'), 0.0))
            dur = end - start
            agg[name] = (total + dur, calls + 1, min(mn, dur), max(mx, dur))
    return agg


def _print_summary(sorted_key=None):
    agg = _aggregate()
    if not agg:
        return
    rows = [(name, calls, total * 1e3, total / calls * 1e3, mn * 1e3,
             mx * 1e3)
            for name, (total, calls, mn, mx) in agg.items()]
    keyfun = {None: lambda r: 0, 'default': lambda r: 0,
              'calls': lambda r: -r[1], 'total': lambda r: -r[2],
              'ave': lambda r: -r[3], 'min': lambda r: -r[4],
              'max': lambda r: -r[5]}[sorted_key]
    rows.sort(key=keyfun)
    print('------------------------->  Profiling Report  '
          '<-------------------------')
    print('%-40s %8s %12s %12s %12s %12s'
          % ('Event', 'Calls', 'Total(ms)', 'Avg(ms)', 'Min(ms)', 'Max(ms)'))
    for r in rows:
        print('%-40s %8d %12.4f %12.4f %12.4f %12.4f' % r)


def _write_chrome_trace(path):
    """chrome://tracing JSON (the reference emits this via tools/timeline.py
    from profiler.proto; we emit it directly)."""
    agg_events = []
    with _lock:
        for name, tid, start, end in _events:
            agg_events.append({
                'name': name, 'cat': 'host', 'ph': 'X',
                'ts': start * 1e6, 'dur': (end - start) * 1e6,
                'pid': 0, 'tid': tid,
            })
    d = os.path.dirname(path)
    if d:
        os.makedirs(d, exist_ok=True)
    with open(path, 'w') as f:
        json.dump({'traceEvents': agg_events}, f)


_HLO_METADATA_RE = None


def hlo_op_map(hlo_texts):
    """instruction-name -> IR-op label, parsed from compiled-HLO
    metadata. Emission wraps every op in jax.named_scope('<type>.<idx>')
    (executor.py seg_fn), so each HLO instruction's op_name path carries
    the IR op that produced it; fusions inherit their root's. This is
    the correlation the reference builds between CUPTI kernel records
    and platform::RecordEvent annotations (device_tracer.cc:81-99)."""
    import re
    global _HLO_METADATA_RE
    if _HLO_METADATA_RE is None:
        _HLO_METADATA_RE = re.compile(
            r'%([\w.-]+) = .*metadata={[^}]*op_name="([^"]+)"')
    scope_re = re.compile(r'([A-Za-z_][\w]*\.\d+)')
    out = {}
    ambiguous = set()
    for text in hlo_texts:
        for m in _HLO_METADATA_RE.finditer(text):
            instr, path = m.group(1), m.group(2)
            ops = scope_re.findall(path)
            if not ops:
                continue
            # instruction names are unique only PER MODULE: when two
            # segments disagree about an instr, drop it (mislabeling
            # device events silently is worse than leaving the raw
            # instruction name)
            if instr in out and out[instr] != ops[-1]:
                ambiguous.add(instr)
            else:
                out[instr] = ops[-1]
    for instr in ambiguous:
        out.pop(instr, None)
    return out


def device_op_events(xplane_dir, op_map=None, with_plane=False):
    """[(label, start_ns, dur_ns)] for every device-side XLA op event in
    an xplane capture, labeled through op_map when the instruction's
    metadata resolves to an IR op. with_plane=True appends the owning
    plane name as a 4th element — one lane per device chip for the
    merged obs timeline (obs/report.py device_events_to_records);
    default stays the 3-tuple shape tools/timeline.py unpacks."""
    import glob
    from jax.profiler import ProfileData
    files = sorted(glob.glob(
        os.path.join(xplane_dir, '**', '*.xplane.pb'), recursive=True))
    events = []
    for fn in files:
        p = ProfileData.from_file(fn)
        for plane in p.planes:
            if not plane.name.startswith('/device:'):
                continue
            for line in plane.lines:
                if line.name != 'XLA Ops':
                    continue
                for e in line.events:
                    instr = e.name.split(' = ')[0].lstrip('%')
                    label = (op_map or {}).get(instr, instr)
                    if with_plane:
                        events.append((label, e.start_ns,
                                       e.duration_ns, plane.name))
                    else:
                        events.append((label, e.start_ns,
                                       e.duration_ns))
    return events


def _dump_segment_hlo(profile_path):
    """Write each live executor's compiled segment HLO next to the
    profile so tools/timeline.py can do the instr->op join offline."""
    import glob
    import shutil
    from .executor import all_compiled_hlo_texts
    hlo_dir = profile_path + '.hlo'
    texts = all_compiled_hlo_texts()
    if not texts:
        return None
    # clear stale segments: leftovers from a previous run at the same
    # path would poison the instr->op join
    if os.path.isdir(hlo_dir):
        shutil.rmtree(hlo_dir)
    os.makedirs(hlo_dir, exist_ok=True)
    for i, t in enumerate(texts):
        with open(os.path.join(hlo_dir, 'segment%03d.txt' % i), 'w') as f:
            f.write(t)
    return hlo_dir


@contextlib.contextmanager
def profiler(state='All', sorted_key=None, profile_path='/tmp/profile'):
    """(reference python profiler.py:221) With a device state, also
    captures an XLA trace to <profile_path>.xplane/ and dumps segment
    HLO to <profile_path>.hlo/; tools/timeline.py --xplane_dir/--hlo_dir
    merges both streams into one chrome trace with per-op device
    slices."""
    start_profiler(state)
    jax_trace = None
    if state in ('GPU', 'All'):
        try:
            import jax
            trace_dir = profile_path + '.xplane'
            # clear stale captures: start_trace APPENDS a new dated run
            # under <dir>/plugins/profile/, and device_op_events globs
            # every *.xplane.pb recursively — a leftover run from an
            # earlier session would silently double-count device time
            # and poison the instr->op join with foreign module names
            if os.path.isdir(trace_dir):
                import shutil
                shutil.rmtree(trace_dir)
            jax.profiler.start_trace(trace_dir)
            jax_trace = trace_dir
        except Exception:
            jax_trace = None
    try:
        yield
    finally:
        if jax_trace is not None:
            try:
                import jax
                jax.profiler.stop_trace()
            except Exception:
                pass
            try:
                _dump_segment_hlo(profile_path)
            except Exception:
                pass
        stop_profiler(sorted_key, profile_path)


@contextlib.contextmanager
def cuda_profiler(output_file, output_mode=None, config=None):
    """API-parity shim for fluid.profiler.cuda_profiler (nvprof control);
    on TPU it degrades to a jax.profiler trace."""
    import jax
    trace_dir = output_file + '.xplane'
    try:
        jax.profiler.start_trace(trace_dir)
        yield
    finally:
        jax.profiler.stop_trace()


def collective_audit(hlo_texts):
    """kind -> [payload bytes] for every collective instruction in the
    given compiled-HLO texts. The ONE audit implementation shared by
    tools/bench_suite.py (scaling-mode collective audit) and the
    BN-local-stats tests, so both count the same spellings: the plain
    and async '-start' forms ('-done' excluded — same collective), with
    tuple outputs (coalesced per-grad all-reduces) counted as one
    instruction whose bytes sum over the tuple."""
    import re
    kinds = ('all-reduce', 'all-gather', 'reduce-scatter',
             'collective-permute', 'all-to-all')
    dt_bytes = {'f32': 4, 'bf16': 2, 's32': 4, 'f16': 2, 'u32': 4,
                'pred': 1, 's64': 8, 'f64': 8}
    kind_re = re.compile(
        r'[)\]}] (all-reduce|all-gather|reduce-scatter|'
        r'collective-permute|all-to-all)(?:-start)?\(')
    colls = {k: [] for k in kinds}
    for text in hlo_texts:
        for line in text.splitlines():
            if ' = ' not in line:
                continue
            _, rhs = line.split(' = ', 1)
            m = kind_re.search(rhs)
            if m is None:
                continue
            nbytes = 0
            for shp in re.finditer(r'([a-z]+\d*)\[([\d,]*)\]',
                                   rhs[:m.start() + 1]):
                dims = [int(d) for d in shp.group(2).split(',') if d]
                sz = 1
                for d in dims:
                    sz *= d
                nbytes += sz * dt_bytes.get(shp.group(1), 4)
            colls[m.group(1)].append(nbytes)
    return {k: v for k, v in colls.items() if v}
