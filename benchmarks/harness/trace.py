"""The traced window: starting and stopping JAX's profiler, reading the
xplane file into plain events, and reducing those to what the layer
metrics read. The reduction works on plain event tuples, so it is
checked on a small recorded trace (tests/data/) without a chip.

An event is (plane, line, name, start_ns, dur_ns). Device planes are
'/device:TPU:<n>'; their 'XLA Ops' line holds one event per executed HLO
instruction, named by the instruction's text, and a `while` event
contains its body's events, so per-op times are SELF times; their 'XLA
Modules' line holds one event per program execution. The profiler keeps
no op_name metadata on the events, so the program's op types come from
the compiled HLO text (`labels_from_hlo`): the executor wraps every op
in jax.named_scope('<type>.<index>'). Host planes hold the threads'
TraceMe events, the benchmark's own `bench.*` spans among them.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
import shutil
import threading
import time

OPS_LINE = 'XLA Ops'
MODULES_LINE = 'XLA Modules'
_SCOPE = re.compile(r'(?:^|/)([A-Za-z_]\w*)\.\d+(?=/|$)')
_COLLECTIVE = re.compile(r'^%?(all-reduce|all-gather|reduce-scatter|'
                         r'all-to-all|collective-permute)')


class Tracer:
    """Traces the last `trace_seconds` of a window. poll() is called by
    the drive loop with the seconds elapsed; stop() after the window.
    Starting the profiler can take seconds the first time, so warm()
    starts and stops it once during set-up, and poll() starts it from a
    thread of its own: an open loop's submitter is never held up."""

    def __init__(self, enabled, out_dir, trace_seconds):
        self.enabled = bool(enabled)
        self.out_dir = out_dir
        self.trace_seconds = float(trace_seconds)
        self.started_at = None
        self.window_s = None
        self._starter = None

    def _start(self):
        import jax
        # a stale capture would be read as this run's
        shutil.rmtree(self.out_dir, ignore_errors=True)
        os.makedirs(self.out_dir, exist_ok=True)
        jax.profiler.start_trace(self.out_dir)
        self.started_at = time.perf_counter()

    def warm(self):
        if self.enabled:
            import jax
            self._start()
            jax.profiler.stop_trace()
            self.started_at = None

    def poll(self, elapsed, seconds):
        if not self.enabled or self._starter is not None \
                or elapsed < seconds - self.trace_seconds:
            return
        self._starter = threading.Thread(target=self._start,
                                         name='bench-tracer')
        self._starter.start()

    def stop(self):
        if self._starter is None or self.window_s is not None:
            return
        import jax
        self._starter.join()
        self.window_s = time.perf_counter() - self.started_at
        jax.profiler.stop_trace()

    def events(self):
        if self.window_s is None:
            return None
        return read_xplane(self.out_dir)


def span(name):
    """A host span on the profiler's clock (free when no trace runs)."""
    import jax
    return jax.profiler.TraceAnnotation(name)


def read_xplane(trace_dir):
    """Every event of the newest capture under trace_dir, as tuples."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(trace_dir, '**', '*.xplane.pb'),
                             recursive=True))
    if not files:
        return []
    out = []
    data = ProfileData.from_file(files[-1])
    for plane in data.planes:
        device = plane.name.startswith('/device:')
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for e in line.events:
                out.append((plane.name, line.name, e.name,
                            int(e.start_ns), int(e.duration_ns)))
    return out


def _instr(name):
    return name.split(' = ')[0].lstrip('%')


def op_label(name, labels):
    """The program's op type where the compiled HLO's metadata names
    one for this instruction, else the instruction's kind
    ('hlo:fusion')."""
    instr = _instr(name)
    if instr in labels:
        return labels[instr]
    return 'hlo:' + re.sub(r'[.\d]+$', '', instr)


_HLO_META = re.compile(r'%([\w.\-]+) = [^\n]*metadata=\{[^}\n]*op_name="([^"]+)"')
_HLO_MODULE = re.compile(r'^HloModule ([\w.\-]+)', re.M)


def labels_from_hlo(events, hlo_texts):
    """{module event name: {instruction: op type}}. Instruction names
    are unique only inside one module, and the trace names a module by
    its jitted function and a fingerprint (`jit_seg_fn(123)`), so each
    traced module takes, of the texts of a module of its name
    (`HloModule jit_seg_fn`), the one that holds the most of the
    instructions seen running inside it. A module of another name takes
    none: a small jitted helper of the program (`jit__with_first`, which
    puts a last chunk's token in front of a decode step) shares
    instruction names with the executor's programs and was counted as an
    execution of the prefill program until PR 53."""
    maps = []
    for text in hlo_texts:
        m = {}
        for instr, path in _HLO_META.findall(text):
            found = _SCOPE.findall(path)
            if found:
                m[instr] = found[-1]
        named = _HLO_MODULE.search(text)
        maps.append((set(re.findall(r'^\s*(?:ROOT )?%([\w.\-]+) = ', text,
                                    re.M)), m,
                     named.group(1) if named else None))
    first = min((e[0] for e in events if e[0].startswith('/device:')),
                default=None)
    ops = sorted((e[3], _instr(e[2])) for e in events
                 if e[0] == first and e[1] == OPS_LINE)
    starts = [s for s, _ in ops]
    # names decide only where the two sides name modules alike at all
    by_name = {sm[2] for sm in maps} & {
        e[2].split('(')[0] for e in events
        if e[0] == first and e[1] == MODULES_LINE}
    out = {}
    for ev in events:
        if ev[0] != first or ev[1] != MODULES_LINE or ev[2] in out:
            continue
        lo = bisect.bisect_left(starts, ev[3])
        hi = bisect.bisect_right(starts, ev[3] + ev[4])
        seen = {i for _, i in ops[lo:hi]}
        mine = ev[2].split('(')[0]
        best = max((sm for sm in maps if not by_name or sm[2] == mine),
                   key=lambda sm: len(seen & sm[0]), default=None)
        out[ev[2]] = best[1] if best and seen & best[0] else {}
    return out


def _union(intervals):
    """Merged, sorted [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _self_times(events):
    """[(event, self_ns)] on one line: duration minus the part covered
    by events nested inside it."""
    out = []
    stack = []                                  # [event, end, child_ns]
    for ev in sorted(events, key=lambda e: (e[3], -e[4])):
        start, dur = ev[3], ev[4]
        while stack and stack[-1][1] <= start:
            done = stack.pop()
            out.append((done[0], max(0, done[0][4] - done[2])))
        if stack:
            stack[-1][2] += dur
        stack.append([ev, start + dur, 0])
    while stack:
        done = stack.pop()
        out.append((done[0], max(0, done[0][4] - done[2])))
    return out


def _subtract(intervals, cover):
    """Total length of `intervals` not covered by merged `cover`."""
    total = 0
    j = 0
    for s, e in intervals:
        cur = s
        while j < len(cover) and cover[j][1] <= cur:
            j += 1
        k = j
        while k < len(cover) and cover[k][0] < e:
            cs, ce = cover[k]
            if cs > cur:
                total += cs - cur
            cur = max(cur, ce)
            if cur >= e:
                break
            k += 1
        if cur < e:
            total += e - cur
    return total


def reduce_events(events, window_s, labels=None, programs=None,
                  span_prefix='bench.'):
    """What the layer metrics read, from plain events:

    busy_s          union of the device op intervals, mean over chips
    window_s        as given (the traced window on the host's clock)
    ops             {label: self seconds}, mean over chips
    collective_s / collective_exposed_s   mean over chips; exposed is
                    collective time during which no other op runs there
    gaps            {name: seconds} idle gaps on the first chip, by the
                    benchmark span the host was in (or after)
    span_calls      {span: how many of the benchmark's host spans}
    programs        {name: {'calls', 'device_s'}} on the first chip: the
                    executions on the 'XLA Modules' line, named by
                    `programs` = {name: [marker op labels]}: an
                    execution that holds the markers of one listed
                    program goes under its name, one that holds those of
                    several under their names joined in the listing's
                    order ('decode+prefill': a step that carried a chunk
                    AND the lanes), never under the first that matches
    op_runs         {label: executions on the first chip that held an op
                    of that label}, whatever program it ran in
    chips           device planes seen

    `labels` is labels_from_hlo()'s map; without it ops keep their HLO
    kinds.
    """
    labels = labels or {}
    planes, modules, spans = {}, {}, []
    for ev in events:
        if ev[0].startswith('/device:'):
            (modules if ev[1] == MODULES_LINE else planes) \
                .setdefault(ev[0], []).append(ev)
        elif ev[2].startswith(span_prefix):
            spans.append((ev[3], ev[3] + ev[4], ev[2]))
    n = len(planes)
    red = {'window_s': window_s, 'chips': n, 'busy_s': 0.0, 'ops': {},
           'collective_s': 0.0, 'collective_exposed_s': 0.0,
           'gaps': {}, 'span_calls': {}, 'programs': {}, 'op_runs': {}}
    if not n:
        return red
    spans.sort()
    for _, _, name in spans:
        red['span_calls'][name] = red['span_calls'].get(name, 0) + 1
    for idx, plane in enumerate(sorted(planes)):
        evs = planes[plane]
        mods = sorted(modules.get(plane, []), key=lambda e: e[3])
        mod_starts = [m[3] for m in mods]

        def label_of(e):
            i = bisect.bisect_right(mod_starts, e[3]) - 1
            inside = i >= 0 and e[3] < mods[i][3] + mods[i][4]
            return op_label(e[2], labels.get(mods[i][2], {})
                            if inside else {})

        selfs = _self_times(evs)
        busy = _union((e[3], e[3] + e[4]) for e in evs)
        red['busy_s'] += sum(e - s for s, e in busy) / 1e9 / n
        coll, others = [], []
        for e, self_ns in selfs:
            label = label_of(e)
            red['ops'][label] = red['ops'].get(label, 0.0) + self_ns / 1e9 / n
            if _COLLECTIVE.match(e[2]):
                coll.append((e[3], e[3] + e[4]))
            elif self_ns == e[4]:                # a leaf, not a container
                others.append((e[3], e[3] + e[4]))
        if coll:
            coll_u = _union(coll)
            red['collective_s'] += sum(e - s for s, e in coll_u) / 1e9 / n
            red['collective_exposed_s'] += \
                _subtract(coll_u, _union(others)) / 1e9 / n
        if idx == 0:
            red['programs'], red['op_runs'] = _programs(
                mods, evs, label_of, programs or {})
            for (_, e1), (s2, _) in zip(busy, busy[1:]):
                gname = _gap_name(spans, e1, s2)
                red['gaps'][gname] = red['gaps'].get(gname, 0.0) \
                    + (s2 - e1) / 1e9
    return red


def _programs(module_events, op_events, label_of, markers):
    """(programs, op_runs): each execution on the modules line, named by
    the listed programs whose marker labels are among the ops that ran
    inside it, and counted once for every label it held."""
    out = {name: {'calls': 0, 'device_s': 0.0} for name in markers}
    runs = {}
    ops = sorted((e[3], label_of(e)) for e in op_events)
    starts = [s for s, _ in ops]
    for ev in module_events:
        lo = bisect.bisect_left(starts, ev[3])
        hi = bisect.bisect_right(starts, ev[3] + ev[4])
        seen = {label for _, label in ops[lo:hi]}
        for label in seen:
            runs[label] = runs.get(label, 0) + 1
        names = [name for name, marks in markers.items()
                 if seen & set(marks)]
        if names:
            p = out.setdefault('+'.join(names),
                               {'calls': 0, 'device_s': 0.0})
            p['calls'] += 1
            p['device_s'] += ev[4] / 1e9
    return out, runs


def carried(programs, name):
    """{'calls', 'device_s'} summed over the executions that carried the
    listed program `name`, alone or joined with others."""
    got = [p for key, p in programs.items() if name in key.split('+')]
    return {'calls': sum(p['calls'] for p in got),
            'device_s': sum(p['device_s'] for p in got)}


def _gap_name(spans, start, end):
    """The benchmark span that covers the gap's middle ('in:<span>'),
    else the last one that ended before it ('after:<span>')."""
    mid = (start + end) // 2
    inside, last = None, None
    for s, e, name in spans:
        if s > mid:
            break
        if e >= mid:
            inside = name
        elif last is None or e > last[0]:
            last = (e, name)
    if inside:
        return 'in:' + inside
    return 'after:' + last[1] if last else 'no_benchmark_span'


def breakdown(red, top=10):
    def top_of(d):
        return [[k, v] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:top]]
    return {'device_ops': top_of(red['ops']),
            'idle_gaps': top_of(red['gaps'])}
