"""Bytes the paged_attention ops of the traced slice have to read (K and V of every live
token, once whatever the number of query heads that share them;
harness/costs_nemotron_h.paged_attention_bytes) over the HBM peak, over the ops' device
time. The ops are those of every execution that held one, in whatever program (`op_runs`);
live tokens a step from the step probe, over the slice's own steps that carried lanes
(builders/gpt2.slice_counts), so bytes and seconds are of the same executions."""
LAYER = 'kernels (pallas/paged_attention.py)'
UNIT = '%'
BETTER = 'higher'
SOURCE = 'device_trace'


from harness import costs_nemotron_h as costs, peaks


def read(run):
    t, c = run['trace'], run['counters']
    op_s = t['ops'].get('paged_attention', 0.0)
    runs = t['op_runs'].get('paged_attention')
    steps = c.get('slice_decode_calls')
    if not op_s or not runs or not steps:
        return None
    ops = runs * costs.kinds(run['config']).count('*')
    need = ops * costs.paged_attention_bytes(
        run['config'], c['slice_live_tokens'] / steps)
    bw = peaks.peaks_of(run['device']['kind'])['hbm_bytes_s']
    return 100.0 * (need / bw) / op_s
