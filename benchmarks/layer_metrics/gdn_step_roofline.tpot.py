"""Bytes the gated_delta_step ops of the traced slice have to move (each live lane's
state read once and written once; harness/costs_hybrid.gdn_step_bytes) over the HBM peak,
over the ops' device time. The ops are those of every execution that held one, in whatever
program (`op_runs`); lanes a step from the program's `state_lanes` attr of the slice's own
steps that carried lanes (builders/gpt2.slice_counts), so bytes and seconds are of the same
executions. Memory-bound: a few hundred kFLOP against 4.4 MB a lane."""
LAYER = 'kernels (ops/delta_rule_ops.py)'
UNIT = '%'
BETTER = 'higher'
SOURCE = 'device_trace'


from harness import costs_hybrid, peaks


def read(run):
    t, c = run['trace'], run['counters']
    op_s = t['ops'].get('gated_delta_step', 0.0)
    runs = t['op_runs'].get('gated_delta_step')
    steps = c.get('slice_decode_calls')
    if not op_s or not runs or not steps or not c.get('slice_state_lanes'):
        return None
    ops = runs * costs_hybrid.kinds(run['config']).count('linear_attention')
    need = ops * costs_hybrid.gdn_step_bytes(
        run['config'], c['slice_state_lanes'] / steps)
    bw = peaks.peaks_of(run['device']['kind'])['hbm_bytes_s']
    return 100.0 * (need / bw) / op_s
