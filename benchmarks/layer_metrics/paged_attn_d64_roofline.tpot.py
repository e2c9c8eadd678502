"""Bytes the paged_attention ops of the traced slice have to read where a page holds 8 K/V
heads of 64, two a lane row (K and V of every live token at 64-wide rows, once whatever the
number of query heads that share them; harness/costs_lfm2.kv_bytes_per_token) over the HBM
peak, over the ops' device time (the kernel runs under the name paged_attention_d64 inside
op paged_attention; the query's widening and the halves' selection around it are in the
op's time). The ops are those of every execution that held one, in whatever program
(`op_runs`); live tokens a step from the step probe, over the slice's own steps that carried
lanes (builders/gpt2.slice_counts)."""
LAYER = 'kernels (pallas/paged_attention.py)'
UNIT = '%'
BETTER = 'higher'
SOURCE = 'device_trace'


from harness import costs_lfm2 as costs, peaks


def read(run):
    t, c = run['trace'], run['counters']
    op_s = t['ops'].get('paged_attention', 0.0)
    runs = t['op_runs'].get('paged_attention')
    steps = c.get('slice_decode_calls')
    if not op_s or not runs or not steps or not c.get('slice_live_tokens'):
        return None
    need = runs * c['slice_live_tokens'] / steps \
        * costs.kv_bytes_per_token(run['config'])
    bw = peaks.peaks_of(run['device']['kind'])['hbm_bytes_s']
    return 100.0 * (need / bw) / op_s
