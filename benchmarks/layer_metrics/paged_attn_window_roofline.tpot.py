"""Bytes the paged_window_attention ops of the traced slice have to read (K and V of the
live window's rows only: each lane's last min(pos + 1, sliding_window_size) tokens, in every
sliding layer; harness/costs_smallthinker.attention_bytes) over the HBM peak, over the ops'
device time. The ops are those of every execution that held one, in whatever program
(`op_runs`); rows a step from the program's `window_rows_read` attr of the slice's own steps
that carried lanes (builders/gpt2.slice_counts): bytes and time from the same executions."""
LAYER = 'kernels (pallas/paged_attention.py)'
UNIT = '%'
BETTER = 'higher'
SOURCE = 'device_trace'


from harness import costs_smallthinker as costs, peaks


def read(run):
    t, c = run['trace'], run['counters']
    op_s = t['ops'].get('paged_window_attention', 0.0)
    runs = t['op_runs'].get('paged_window_attention')
    steps = c.get('slice_decode_calls')
    if not op_s or not runs or not steps \
            or not c.get('slice_window_rows_read'):
        return None
    need = runs * costs.layers(run['config'])[1] \
        * costs.attention_bytes(run['config'],
                                c['slice_window_rows_read'] / steps)
    bw = peaks.peaks_of(run['device']['kind'])['hbm_bytes_s']
    return 100.0 * (need / bw) / op_s
