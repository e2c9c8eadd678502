"""Bytes the paged_attention ops of the traced window have to read (K and V of every live
token, once whatever the number of query heads that share them;
harness/costs_nemotron_h.paged_attention_bytes, live tokens a step from the benchmark's
step probe) over the HBM peak, over the ops' device time."""
LAYER = 'kernels (pallas/paged_attention.py)'
UNIT = '%'
BETTER = 'higher'
SOURCE = 'device_trace'


from harness import costs_nemotron_h as costs, peaks


def read(run):
    t, c = run['trace'], run['counters']
    op_s = t['ops'].get('paged_attention', 0.0)
    p = t['programs'].get('decode')
    if not op_s or not p or not p['calls'] or not c.get('decode_calls'):
        return None
    live = c['live_tokens'] / c['decode_calls']        # mean a step
    ops = p['calls'] * costs.kinds(run['config']).count('*')
    need = ops * costs.paged_attention_bytes(run['config'], live)
    bw = peaks.peaks_of(run['device']['kind'])['hbm_bytes_s']
    return 100.0 * (need / bw) / op_s
