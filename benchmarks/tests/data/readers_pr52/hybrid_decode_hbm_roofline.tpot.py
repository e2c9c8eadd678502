"""Bytes a decode step of the hybrid block has to move (float32 weights once, K/V of the
live tokens in the full_attention layers, the live lanes' recurrent state read and
written; harness/costs_hybrid.decode_step_bytes) over the HBM peak, over the decode
program's device time. Memory-bound: one token per lane."""
LAYER = 'kernels (decode program)'
UNIT = '%'
BETTER = 'higher'
SOURCE = 'device_trace'


from harness import costs_hybrid, peaks


def read(run):
    p = run['trace']['programs'].get('decode')
    c = run['counters']
    if not p or not p['calls'] or not c.get('decode_calls') \
            or 'state_lanes' not in c:
        return None
    need = costs_hybrid.decode_step_bytes(
        run['config'], c['live_tokens'] / c['decode_calls'],
        c['state_lanes'] / c['decode_calls'])
    bw = peaks.peaks_of(run['device']['kind'])['hbm_bytes_s']
    return 100.0 * (need / bw) / (p['device_s'] / p['calls'])
