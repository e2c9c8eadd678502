"""Bytes the paged_attention ops of the traced window have to read where a page holds 4 K/V
heads (K and V of every live token of the global layers, once whatever the number of query
heads that share them; harness/costs_smallthinker.attention_bytes; rows a step from the
decode steps of the traced slice's own seconds) over the HBM peak, over the ops' device
time. The sliding layers' calls go by another op type and are not in it."""
LAYER = 'kernels (pallas/paged_attention.py)'
UNIT = '%'
BETTER = 'higher'
SOURCE = 'device_trace'


from harness import costs_smallthinker as costs, peaks


def read(run):
    t, c = run['trace'], run['counters']
    op_s = t['ops'].get('paged_attention', 0.0)
    p = t['programs'].get('decode')
    steps = c.get('slice_decode_calls')
    if not op_s or not p or not p['calls'] or not steps \
            or 'slice_full_rows_read' not in c:
        return None
    need = p['calls'] * costs.layers(run['config'])[0] \
        * costs.attention_bytes(run['config'],
                                c['slice_full_rows_read'] / steps)
    bw = peaks.peaks_of(run['device']['kind'])['hbm_bytes_s']
    return 100.0 * (need / bw) / op_s
