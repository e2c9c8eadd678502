"""Bytes the kda_step ops of the traced slice have to move (each live lane's delta state read
once and written once; harness/costs_solar2.kda_step_bytes) over the HBM peak, over the ops'
device time. Lanes a step from the decode steps of the slice's own seconds (the builder's
`slice_state_lanes` over `slice_decode_calls`), so bytes and seconds are of the same
executions: a mean over the whole window read gdn_step_roofline.tpot over 100 % where a slice
held fewer lanes. Memory-bound: a few hundred kFLOP against 4.2 MB a lane a layer."""
LAYER = 'kernels (ops/delta_rule_ops.py)'
UNIT = '%'
BETTER = 'higher'
SOURCE = 'device_trace'


from harness import costs_solar2 as costs, peaks


def read(run):
    t, c = run['trace'], run['counters']
    op_s = t['ops'].get('kda_step', 0.0)
    p = t['programs'].get('decode')
    steps = c.get('slice_decode_calls')
    if not op_s or not p or not p['calls'] or not steps \
            or not c.get('slice_state_lanes'):
        return None
    ops = p['calls'] * costs.kinds(run['config']).count('kda')
    need = ops * costs.kda_step_bytes(run['config'],
                                      c['slice_state_lanes'] / steps)
    bw = peaks.peaks_of(run['device']['kind'])['hbm_bytes_s']
    return 100.0 * (need / bw) / op_s
