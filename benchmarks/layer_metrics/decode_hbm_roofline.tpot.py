"""Bytes a decode step has to read (float32 weights + K/V of the live tokens, from
shapes; harness/costs.decode_step_bytes; live tokens a step a mean over the traced slice's
steps that carried lanes and no chunk, builders/gpt2.slice_counts' `slice_plain_*`) over the
HBM peak, over the decode program's device time in the same slice. Memory-bound: one token
per lane."""
LAYER = 'kernels (decode program)'
UNIT = '%'
BETTER = 'higher'
SOURCE = 'device_trace'


from harness import costs, peaks


def read(run):
    p = run['trace']['programs'].get('decode')
    c = run['counters']
    steps = c.get('slice_plain_decode_calls')
    if not p or not p['calls'] or not steps:
        return None
    need = costs.decode_step_bytes(run['config'],
                                   c['slice_plain_live_tokens'] / steps)
    bw = peaks.peaks_of(run['device']['kind'])['hbm_bytes_s']
    return 100.0 * (need / bw) / (p['device_s'] / p['calls'])
