"""Bytes the paged_attention ops of the traced slice have to read where a page holds 4 K/V
heads (K and V of every live token of the global layers, once whatever the number of query
heads that share them; harness/costs_smallthinker.attention_bytes) over the HBM peak, over
the ops' device time. The ops are those of every execution that held one, in whatever
program (`op_runs`); rows a step from the program's `rows_read` attr of the slice's own
steps that carried lanes (builders/gpt2.slice_counts). The sliding layers' calls go by
another op type and are not in it."""
LAYER = 'kernels (pallas/paged_attention.py)'
UNIT = '%'
BETTER = 'higher'
SOURCE = 'device_trace'


from harness import costs_smallthinker as costs, peaks


def read(run):
    t, c = run['trace'], run['counters']
    op_s = t['ops'].get('paged_attention', 0.0)
    runs = t['op_runs'].get('paged_attention')
    steps = c.get('slice_decode_calls')
    if not op_s or not runs or not steps or not c.get('slice_rows_read'):
        return None
    need = runs * costs.layers(run['config'])[0] \
        * costs.attention_bytes(run['config'], c['slice_rows_read'] / steps)
    bw = peaks.peaks_of(run['device']['kind'])['hbm_bytes_s']
    return 100.0 * (need / bw) / op_s
