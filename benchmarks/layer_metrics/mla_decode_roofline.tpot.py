"""Bytes the paged_latent_attention ops of the traced slice have to read (the latent row of
every live token once, 576 values whatever the number of heads: harness/costs_axk1) over
the HBM peak, over the ops' device time. The ops are those of every execution that held one,
in whatever program (`op_runs`); rows a step from the program's `latent_rows` attr of the
slice's own steps that carried lanes (builders/gpt2.slice_counts), so bytes and time come
from the same executions. The ops' time holds the absorbed query and the output's
up-projection too (W_UKV, 34 MB a layer, is not counted as needed)."""
LAYER = 'kernels (pallas/paged_attention.py)'
UNIT = '%'
BETTER = 'higher'
SOURCE = 'device_trace'


from harness import costs_axk1 as costs, peaks


def read(run):
    t, c = run['trace'], run['counters']
    op_s = t['ops'].get('paged_latent_attention', 0.0)
    runs = t['op_runs'].get('paged_latent_attention')
    steps = c.get('slice_decode_calls')
    if not op_s or not runs or not steps or not c.get('slice_latent_rows'):
        return None
    need = runs * costs.mla_decode_bytes(run['config'],
                                         c['slice_latent_rows'] / steps)
    bw = peaks.peaks_of(run['device']['kind'])['hbm_bytes_s']
    return 100.0 * (need / bw) / op_s
