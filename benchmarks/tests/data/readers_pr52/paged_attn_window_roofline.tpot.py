"""Bytes the paged_window_attention ops of the traced window have to read (K and V of the
live window's rows only: each lane's last min(pos + 1, sliding_window_size) tokens, in every
sliding layer; harness/costs_smallthinker.attention_bytes; rows a step from the decode steps
of the traced slice's own seconds, builders/smallthinker.py's `slice_*` counters) over the
HBM peak, over the ops' device time: bytes and time from the same executions."""
LAYER = 'kernels (pallas/paged_attention.py)'
UNIT = '%'
BETTER = 'higher'
SOURCE = 'device_trace'


from harness import costs_smallthinker as costs, peaks


def read(run):
    t, c = run['trace'], run['counters']
    op_s = t['ops'].get('paged_window_attention', 0.0)
    p = t['programs'].get('decode')
    steps = c.get('slice_decode_calls')
    if not op_s or not p or not p['calls'] or not steps \
            or 'slice_window_rows_read' not in c:
        return None
    need = p['calls'] * costs.layers(run['config'])[1] \
        * costs.attention_bytes(run['config'],
                                c['slice_window_rows_read'] / steps)
    bw = peaks.peaks_of(run['device']['kind'])['hbm_bytes_s']
    return 100.0 * (need / bw) / op_s
