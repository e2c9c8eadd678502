"""Performance regression gate over a trajectory of result files.

A trajectory is a set of BENCH_r*.json-shaped files, each `{"n":
round, "cmd": ..., "parsed": {metric: value, ...}}`, named by
--bench-glob. This tool diffs a candidate metric set against that
trajectory and exits nonzero when any shared metric regresses beyond
tolerance. The repository commits no trajectory: the records it once
held were taken on an earlier machine, older than the code, and were
deleted; the driver's PERF_LEDGER.jsonl is the record now, and
ROADMAP S1 rebuilds this gate on the benchmark's cell table.

Modes:

    python tools/perf_gate.py --bench-glob 'dir/BENCH_r*.json'
        gate the NEWEST round of the trajectory against the best prior
        value of every metric (per-metric: rounds may add/drop metrics;
        only metrics present on both sides compare)

    python tools/perf_gate.py --candidate cand.json --bench-glob ...
        gate a fresh result file (BENCH wrapper or a bare
        {metric: value} dict) against the whole trajectory

    python tools/perf_gate.py --run-suite [--baseline base.json]
        run `tools/bench_suite.py --quick` now, stamp its rows (incl.
        the obs-gauge compile_ms/hbm_peak columns) into a metric
        set, and gate it against --baseline (a previous --save file)

    python tools/perf_gate.py --smoke
        self-test the gate mechanics on synthetic fixtures (CPU-safe,
        fast; tier-1 runs this) — exits nonzero iff the mechanics are
        broken

Direction is inferred from the metric name (suffix match): throughput/
MFU/speedup metrics must not DROP, latency/footprint metrics must not
GROW. Unrecognized or non-numeric metrics are reported as skipped, not
gated. Default tolerance 5%; per-metric overrides widen it where the
committed trajectory itself documents run-to-run spread (longcontext
chip-window placement: ~11% between identical runs, PERF.md round 5).
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# suffix -> direction: +1 = higher is better, -1 = lower is better
_HIGHER = ('_per_sec', 'mfu', 'value', 'tflops', 'speedup',
           'vs_baseline', 'samples_per_sec', 'efficiency', 'hits',
           '_accept_rate', '_completion_rate',
           '_win_rate', '_hit_rate', '_per_chip')
_LOWER = ('_ms', '_secs', 'compile_ms', 'hbm_peak', 'peak_hbm_gb',
          '_bytes', 'misses', 'latency', '_hbm_per_chip_mb')

TOL_DEFAULT = 0.05
# longcontext numbers moved ~11% between identical runs on the shared
# chip of an earlier machine (PERF.md round 5); allocator peaks wobble
# with XLA's buffer assignment
TOL_OVERRIDES = {
    'longcontext_tokens_per_sec': 0.15,
    'longcontext_tflops_per_sec': 0.15,
    'longcontext_mfu': 0.15,
    'hbm_peak': 0.25,
    'compile_ms': 0.50,   # host-load sensitive
}

def metric_direction(name):
    """+1 (higher better), -1 (lower better), or None (ungated)."""
    for suf in _LOWER:
        if name.endswith(suf):
            return -1
    for suf in _HIGHER:
        if name.endswith(suf):
            return 1
    return None


def metric_tolerance(name, default=TOL_DEFAULT):
    for key, tol in TOL_OVERRIDES.items():
        if name.endswith(key):
            return tol
    return default


def load_metrics(path_or_dict):
    """{metric: float} from a BENCH_r*.json wrapper ({'parsed': ...}),
    a bare metric dict, or a dict already in hand. Non-numeric values
    (configs, units, notes) are dropped; bools are not numbers here."""
    d = path_or_dict
    if isinstance(d, str):
        with open(d) as f:
            d = json.load(f)
    if 'parsed' in d and isinstance(d['parsed'], dict):
        d = d['parsed']
    out = {}
    for name, v in d.items():
        if isinstance(v, bool) or not isinstance(v, (int, float)):
            continue
        out[name] = float(v)
    return out


def gate(reference_sets, candidate, default_tol=TOL_DEFAULT):
    """Compare candidate against the per-metric BEST across the
    reference sets. -> (failures, checked, skipped) where failures is
    [(metric, candidate_value, best_reference, allowed_limit)]."""
    best = {}
    for ref in reference_sets:
        for name, v in ref.items():
            if metric_direction(name) is None:
                continue
            if name not in best:
                best[name] = v
            elif metric_direction(name) > 0:
                best[name] = max(best[name], v)
            else:
                best[name] = min(best[name], v)
    failures, checked, skipped = [], [], []
    for name, cand in sorted(candidate.items()):
        direction = metric_direction(name)
        if direction is None:
            skipped.append(name)
            continue
        if name not in best:
            continue   # new metric: nothing to regress against
        ref = best[name]
        tol = metric_tolerance(name, default_tol)
        if ref == 0:
            continue
        if direction > 0:
            limit = ref * (1.0 - tol)
            ok = cand >= limit
        else:
            limit = ref * (1.0 + tol)
            ok = cand <= limit
        checked.append(name)
        if not ok:
            failures.append((name, cand, ref, limit))
    return failures, checked, skipped


def bench_files(pattern):
    return sorted(glob.glob(pattern)) if pattern else []


def run_suite(steps=None):
    """Fresh `bench_suite --quick` -> {metric: value} (row fields
    flattened as <model>_<mode>_<field>)."""
    cmd = [sys.executable, os.path.join(REPO, 'tools', 'bench_suite.py'),
           '--quick', '--json']
    if steps:
        cmd += ['--steps', str(steps)]
    out = subprocess.run(cmd, capture_output=True, text=True,
                         cwd=REPO)
    if out.returncode != 0:
        raise RuntimeError('bench_suite --quick failed:\n%s'
                           % (out.stderr or out.stdout)[-2000:])
    rows = json.loads(out.stdout.splitlines()[-1])
    metrics = {}
    for row in rows:
        prefix = '%s_%s' % (row.get('model'), row.get('mode'))
        for field, v in row.items():
            if field in ('model', 'mode') or isinstance(v, bool) \
                    or not isinstance(v, (int, float)):
                continue
            metrics['%s_%s' % (prefix, field)] = float(v)
    return metrics


def smoke():
    """Gate-mechanics self-test on synthetic fixtures; returns the
    number of broken mechanics (0 = healthy)."""
    bad = 0
    total = 0

    def expect(cond, what):
        nonlocal bad, total
        total += 1
        if not cond:
            bad += 1
            print('smoke FAIL: %s' % what)

    traj = [{'mfu': 0.25, 'value': 100.0, 'decode_p99_ms': 10.0},
            {'mfu': 0.28, 'value': 110.0, 'decode_p99_ms': 9.0}]
    ok_cand = {'mfu': 0.275, 'value': 109.0, 'decode_p99_ms': 9.2}
    fails, checked, _ = gate(traj, ok_cand)
    expect(not fails and len(checked) == 3,
           'healthy candidate flagged: %r' % fails)
    # >5% mfu drop must trip
    fails, _, _ = gate(traj, {'mfu': 0.20})
    expect(any(f[0] == 'mfu' for f in fails), 'mfu regression missed')
    # lower-is-better: latency growth must trip, improvement must not
    fails, _, _ = gate(traj, {'decode_p99_ms': 12.0})
    expect(any(f[0] == 'decode_p99_ms' for f in fails),
           'latency regression missed')
    fails, _, _ = gate(traj, {'decode_p99_ms': 5.0})
    expect(not fails, 'latency improvement flagged')
    # unknown-direction metrics are skipped, never gated
    _, _, skipped = gate(traj, {'some_config': 3.0})
    expect(skipped == ['some_config'], 'direction inference leak')
    # gray-failure leg metrics (serve_bench --hedge): hedge_win_rate
    # is higher-better, degraded_p99_ttft_ms rides the _ms ceiling
    traj_gray = [{'hedge_win_rate': 0.9, 'degraded_p99_ttft_ms': 400.0}]
    fails, _, _ = gate(traj_gray, {'hedge_win_rate': 0.5,
                                   'degraded_p99_ttft_ms': 390.0})
    expect(any(f[0] == 'hedge_win_rate' for f in fails),
           'hedge_win_rate collapse missed')
    fails, _, _ = gate(traj_gray, {'hedge_win_rate': 0.92,
                                   'degraded_p99_ttft_ms': 900.0})
    expect(any(f[0] == 'degraded_p99_ttft_ms' for f in fails),
           'degraded TTFT regression missed')
    fails, _, _ = gate(traj_gray, {'hedge_win_rate': 0.88,
                                   'degraded_p99_ttft_ms': 200.0})
    expect(not fails, 'healthy gray-failure metrics flagged: %r' % fails)
    # disagg leg metrics (serve_bench --disagg): fleet_prefix_hit_rate
    # is higher-better, disagg_p99_ttft_ms rides the _ms ceiling
    traj_dis = [{'fleet_prefix_hit_rate': 0.85,
                 'disagg_p99_ttft_ms': 120.0}]
    fails, _, _ = gate(traj_dis, {'fleet_prefix_hit_rate': 0.4,
                                  'disagg_p99_ttft_ms': 115.0})
    expect(any(f[0] == 'fleet_prefix_hit_rate' for f in fails),
           'prefix hit-rate collapse missed')
    fails, _, _ = gate(traj_dis, {'fleet_prefix_hit_rate': 0.9,
                                  'disagg_p99_ttft_ms': 300.0})
    expect(any(f[0] == 'disagg_p99_ttft_ms' for f in fails),
           'disagg TTFT regression missed')
    fails, _, _ = gate(traj_dis, {'fleet_prefix_hit_rate': 0.84,
                                  'disagg_p99_ttft_ms': 110.0})
    expect(not fails, 'healthy disagg metrics flagged: %r' % fails)
    # mesh leg metrics (serve_bench --mesh): aggregate AND per-chip
    # throughput gate as higher-better (a mesh that holds aggregate by
    # burning N more chips must trip on _per_chip); the per-chip HBM
    # footprint rides a lower-is-better ceiling
    traj_mesh = [{'mesh_tokens_per_sec': 2000.0,
                  'mesh_tokens_per_sec_per_chip': 1000.0,
                  'mesh_hbm_per_chip_mb': 50.0}]
    fails, _, _ = gate(traj_mesh, {'mesh_tokens_per_sec': 2100.0,
                                   'mesh_tokens_per_sec_per_chip': 500.0,
                                   'mesh_hbm_per_chip_mb': 49.0})
    expect(any(f[0] == 'mesh_tokens_per_sec_per_chip' for f in fails),
           'per-chip throughput collapse missed')
    fails, _, _ = gate(traj_mesh, {'mesh_tokens_per_sec': 1500.0,
                                   'mesh_tokens_per_sec_per_chip': 990.0,
                                   'mesh_hbm_per_chip_mb': 50.0})
    expect(any(f[0] == 'mesh_tokens_per_sec' for f in fails),
           'mesh aggregate throughput regression missed')
    fails, _, _ = gate(traj_mesh, {'mesh_tokens_per_sec': 2000.0,
                                   'mesh_tokens_per_sec_per_chip': 1000.0,
                                   'mesh_hbm_per_chip_mb': 90.0})
    expect(any(f[0] == 'mesh_hbm_per_chip_mb' for f in fails),
           'per-chip HBM growth missed')
    fails, _, _ = gate(traj_mesh, {'mesh_tokens_per_sec': 1990.0,
                                   'mesh_tokens_per_sec_per_chip': 996.0,
                                   'mesh_hbm_per_chip_mb': 48.0})
    expect(not fails, 'healthy mesh metrics flagged: %r' % fails)
    # per-metric tolerance override: longcontext 11% swing passes
    traj2 = [{'longcontext_mfu': 0.46}]
    fails, _, _ = gate(traj2, {'longcontext_mfu': 0.41})
    expect(not fails, 'longcontext tolerance override lost')
    print('smoke: %s (%d mechanics checks)'
          % ('ok' if bad == 0 else '%d FAILURES' % bad, total))
    return bad


def report(failures, checked, skipped, label):
    print('perf_gate: %s — %d metric(s) gated, %d skipped '
          '(no direction)' % (label, len(checked), len(skipped)))
    for name, cand, ref, limit in failures:
        arrow = 'below floor' if metric_direction(name) > 0 \
            else 'above ceiling'
        print('  REGRESSION %-38s %.4g %s %.4g (best prior %.4g)'
              % (name, cand, arrow, limit, ref))
    if not failures:
        print('  no regressions beyond tolerance')


def main(argv=None):
    ap = argparse.ArgumentParser(
        description=__doc__.splitlines()[0])
    ap.add_argument('--candidate', default=None,
                    help='gate this result file instead of the newest '
                         'round of the trajectory')
    ap.add_argument('--bench-glob', default=None,
                    help='the trajectory: a glob of BENCH_r*.json-shaped '
                         'files (the repository commits none)')
    ap.add_argument('--run-suite', action='store_true',
                    help='run bench_suite --quick and gate its rows')
    ap.add_argument('--baseline', default=None,
                    help='reference metric file for --run-suite: a '
                         '--save file from the same machine')
    ap.add_argument('--save', default=None,
                    help='write the candidate metric set here (json) '
                         'for use as a later --baseline')
    ap.add_argument('--steps', type=int, default=None,
                    help='bench_suite --steps passthrough')
    ap.add_argument('--tolerance', type=float, default=TOL_DEFAULT)
    ap.add_argument('--smoke', action='store_true',
                    help='self-test gate mechanics on synthetic '
                         'fixtures and exit')
    args = ap.parse_args(argv)

    if args.smoke:
        return 1 if smoke() else 0
    if not (args.run_suite or args.candidate or args.bench_glob):
        ap.error('nothing to gate: pass --bench-glob (a trajectory), '
                 '--candidate, --run-suite or --smoke')

    if args.run_suite:
        candidate = run_suite(steps=args.steps)
        label = 'bench_suite --quick'
        if args.baseline:
            refs = [load_metrics(args.baseline)]
        else:
            refs = [load_metrics(p) for p in
                    bench_files(args.bench_glob)]
    else:
        files = bench_files(args.bench_glob)
        if args.candidate:
            candidate = load_metrics(args.candidate)
            label = args.candidate
            refs = [load_metrics(p) for p in files]
        else:
            if len(files) < 2:
                print('perf_gate: <2 rounds in trajectory, nothing to '
                      'gate')
                return 0
            candidate = load_metrics(files[-1])
            label = os.path.basename(files[-1])
            refs = [load_metrics(p) for p in files[:-1]]

    if args.save:
        with open(args.save, 'w') as f:
            json.dump(candidate, f, indent=2)
        print('perf_gate: saved candidate metrics -> %s' % args.save)

    if not refs or not any(refs):
        print('perf_gate: no reference metrics, nothing to gate')
        return 0
    failures, checked, skipped = gate(refs, candidate,
                                      default_tol=args.tolerance)
    report(failures, checked, skipped, label)
    return 1 if failures else 0


if __name__ == '__main__':
    sys.exit(main())
