"""Bytes the gated_delta_step ops of the traced window have to move (each live lane's
state read once and written once; harness/costs_hybrid.gdn_step_bytes, lanes from the
program's `serving.state_lanes` counter) over the HBM peak, over the ops' device time.
Memory-bound: a few hundred kFLOP against 4.4 MB a lane."""
LAYER = 'kernels (ops/delta_rule_ops.py)'
UNIT = '%'
BETTER = 'higher'
SOURCE = 'device_trace'


from harness import costs_hybrid, peaks


def read(run):
    t, c = run['trace'], run['counters']
    op_s = t['ops'].get('gated_delta_step', 0.0)
    p = t['programs'].get('decode')
    if not op_s or not p or not p['calls'] or not c.get('decode_calls') \
            or not c.get('state_lanes'):
        return None
    lanes = c['state_lanes'] / c['decode_calls']       # mean a step
    ops = p['calls'] * costs_hybrid.kinds(run['config']).count(
        'linear_attention')
    need = ops * costs_hybrid.gdn_step_bytes(run['config'], lanes)
    bw = peaks.peaks_of(run['device']['kind'])['hbm_bytes_s']
    return 100.0 * (need / bw) / op_s
