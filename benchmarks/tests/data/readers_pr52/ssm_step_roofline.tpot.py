"""The least time the chip could take for the ssd_step ops of the traced window (the larger
of their bytes over the HBM peak, each live lane's state read once and written once, and
their FLOPs over the bf16 peak; harness/costs_nemotron_h, lanes from the program's
`serving.state_lanes` counter) over the ops' device time. Memory-bound: 4 FLOP for 8 bytes."""
LAYER = 'kernels (ops/ssd_ops.py)'
UNIT = '%'
BETTER = 'higher'
SOURCE = 'device_trace'


from harness import costs_nemotron_h as costs, peaks


def read(run):
    t, c = run['trace'], run['counters']
    op_s = t['ops'].get('ssd_step', 0.0)
    p = t['programs'].get('decode')
    if not op_s or not p or not p['calls'] or not c.get('decode_calls') \
            or not c.get('state_lanes'):
        return None
    lanes = c['state_lanes'] / c['decode_calls']       # mean a step
    peak = peaks.peaks_of(run['device']['kind'])
    least = max(costs.ssd_step_bytes(run['config'], lanes)
                / peak['hbm_bytes_s'],
                costs.ssd_step_flops(run['config'], lanes)
                / peak['bf16_flops'])
    ops = p['calls'] * costs.kinds(run['config']).count('M')
    return 100.0 * ops * least / op_s
