"""The measured window, one loop per kind of traffic. A drive takes
(system, plan, seconds, tracer) and returns
{'e2e': {metric: value}, 'attempted': n, 'failed': n, 'counters': {...}}.
The end-to-end metrics are computed here, from the host's clock and
from `Request` timestamps, never read from the program's histograms.
"""
from __future__ import annotations

import statistics
import time

import numpy as np

from . import trace


def _percentile(values, q):
    """Nearest-rank percentile of all values."""
    vals = sorted(values)
    return vals[min(len(vals) - 1, max(0, int(np.ceil(q * len(vals))) - 1))]


def steps(system, plan, seconds, tracer):
    """Training: blocked steps until `seconds` have passed; the rate is
    all items over all the time."""
    before = system.counters()
    step_s, depth = [], []
    t0 = time.perf_counter()
    while True:
        now = time.perf_counter()
        if now - t0 >= seconds:
            break
        tracer.poll(now - t0, seconds)
        depth.append(system.reader_depth())
        with trace.span('bench.train_step'):
            system.step()
        step_s.append(time.perf_counter() - now)
    elapsed = time.perf_counter() - t0
    tracer.stop()
    after = system.counters()
    items = len(step_s) * system.items_per_step
    return {
        'e2e': {'train_items_s': items / elapsed},
        'attempted': len(step_s), 'failed': 0,
        'counters': {
            'steps': len(step_s), 'window_s': elapsed,
            'step_ms_p50': 1e3 * statistics.median(step_s),
            'step_ms_max': 1e3 * max(step_s),
            'step_max_at': step_s.index(max(step_s)),
            'reader_dev_queue_min': min(depth),
            'compiles_in_window': after['compiled_segments']
            - before['compiled_segments']}}


def _serve_counters(system, before, after, t_window):
    """Totals as after minus before; a `*_max` is the most since the
    reading before it and a `slice_*` a sum over the last seconds
    before its reading (the traced slice's own counts): both stand as
    `after` has them."""
    c = {k: after[k] if k.endswith('_max') or k.startswith('slice_')
         else after[k] - before[k] for k in after}
    c['window_s'] = t_window
    # the name the training drive gives it: one reader reads both
    c['compiles_in_window'] = c.pop('compiled_segments')
    return c


def open_loop(system, plan, seconds, tracer):
    """Requests submitted when due. Judged: those due inside the window.
    The closing reading of the system's counters is told when the
    profiler's capture began (None without one), so that the slice's own
    counts are of the steps the traced slice holds.
    Arrivals go on after it until the judged have all ended or the
    time-out has passed, so the last judged request decodes under the
    same load as the first."""
    eng = system.engine
    judged, everyone = [], []
    before = system.counters()
    after = None
    t0 = time.perf_counter()
    deadline = t0 + seconds + plan['timeout_s']
    for i, r in enumerate(plan['requests']):
        due = t0 + r['due']
        tail = i >= plan['judged']
        if tail and (all(q.done_at is not None for _, q in judged)
                     or due > deadline):
            break
        delay = due - time.perf_counter()
        if delay > 0:
            time.sleep(delay)
        if after is None and time.perf_counter() - t0 >= seconds:
            after = system.counters(tracer.started_at)
            tracer.stop()
        tracer.poll(time.perf_counter() - t0, seconds)
        try:
            req = eng.submit(r['prompt'], max_new_tokens=r['max_new'])
        except RuntimeError:            # refused: queue full or draining
            req = None
        everyone.append(req)
        if not tail:
            judged.append((due, req))
    end = t0 + seconds
    if time.perf_counter() < end:
        time.sleep(end - time.perf_counter())
    if after is None:
        after = system.counters(tracer.started_at)
        tracer.stop()
    for _, req in judged:
        if req is not None:
            req.wait(max(0.0, deadline - time.perf_counter()))
    ttft, tpot, late, failed = [], [], [], 0
    for due, req in judged:
        if req is None or req.state != 'DONE' or len(req.tokens) < 2:
            failed += 1
            continue
        ttft.append(req.first_token_at - due)
        tpot.append((req.done_at - req.first_token_at)
                    / (len(req.tokens) - 1))
        late.append(req.submitted_at - due)
    counters = _serve_counters(system, before, after, seconds)
    counters['gen_late_p95_ms'] = 1e3 * _percentile(late, 0.95) \
        if late else None
    e2e = {}
    if ttft:
        # a failed request misses every limit: it counts as the worst
        worst = [seconds + plan['timeout_s']] * failed
        e2e = {'tpot_p50_ms': 1e3 * _percentile(tpot + worst, 0.50)}
        counters['ttft_p90_ms'] = 1e3 * _percentile(ttft + worst, 0.90)
        counters['ttft_p50_ms'] = 1e3 * _percentile(ttft + worst, 0.50)
    return {'e2e': e2e, 'attempted': len(judged), 'failed': failed,
            'counters': counters, 't0': t0,
            'requests': [r for r in everyone if r is not None]}
