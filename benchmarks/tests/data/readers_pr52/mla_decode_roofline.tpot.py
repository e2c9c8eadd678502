"""Bytes the paged_latent_attention ops of the traced slice have to read (the latent row of
every live token once, 576 values whatever the number of heads: harness/costs_axk1;
rows a step from the program's `latent_rows` attr of the decode steps dispatched in the
slice's own seconds, so bytes and time come from the same executions) over the HBM peak,
over the ops' device time. The ops' time holds the absorbed query and the output's
up-projection too (W_UKV, 34 MB a layer, is not counted as needed)."""
LAYER = 'kernels (pallas/paged_attention.py)'
UNIT = '%'
BETTER = 'higher'
SOURCE = 'device_trace'


from harness import costs_axk1 as costs, peaks


def read(run):
    t, c = run['trace'], run['counters']
    op_s = t['ops'].get('paged_latent_attention', 0.0)
    p = t['programs'].get('decode')
    if not op_s or not p or not p['calls'] \
            or not c.get('slice_decode_calls_max'):
        return None
    rows = c['slice_latent_rows_max'] / c['slice_decode_calls_max']
    need = p['calls'] * costs.mla_decode_bytes(run['config'], rows)
    bw = peaks.peaks_of(run['device']['kind'])['hbm_bytes_s']
    return 100.0 * (need / bw) / op_s
