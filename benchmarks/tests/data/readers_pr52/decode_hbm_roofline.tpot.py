"""Bytes a decode step has to read (float32 weights + K/V of the live tokens, from
shapes; harness/costs.decode_step_bytes) over the HBM peak, over the decode
program's device time. Memory-bound: one token per lane."""
LAYER = 'kernels (decode program)'
UNIT = '%'
BETTER = 'higher'
SOURCE = 'device_trace'


from harness import costs, peaks


def read(run):
    p = run['trace']['programs'].get('decode')
    c = run['counters']
    if not p or not p['calls'] or not c.get('decode_calls'):
        return None
    live = c['live_tokens'] / c['decode_calls']
    need = costs.decode_step_bytes(run['config'], live)
    bw = peaks.peaks_of(run['device']['kind'])['hbm_bytes_s']
    return 100.0 * (need / bw) / (p['device_s'] / p['calls'])
