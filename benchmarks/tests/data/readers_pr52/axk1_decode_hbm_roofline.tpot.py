"""Bytes a decode step of the A.X-K1 block has to move (float32 weights outside the routed
experts once, the held experts its lanes chose, the latent rows of the live tokens in every
layer; harness/costs_axk1.decode_step_bytes: experts a layer a mean over the window's steps,
rows a step from the decode steps of the traced slice's own seconds) over the HBM peak,
over the decode program's device time. Memory-bound: one token a lane."""
LAYER = 'kernels (decode program)'
UNIT = '%'
BETTER = 'higher'
SOURCE = 'device_trace'


from harness import costs_axk1 as costs, peaks


def read(run):
    p = run['trace']['programs'].get('decode')
    c = run['counters']
    if not p or not p['calls'] or not c.get('moe_layer_calls') \
            or not c.get('slice_decode_calls_max'):
        return None
    need = costs.decode_step_bytes(
        run['config'],
        c['slice_latent_rows_max'] / c['slice_decode_calls_max'],
        c['moe_experts_touched'] / c['moe_layer_calls'])
    bw = peaks.peaks_of(run['device']['kind'])['hbm_bytes_s']
    return 100.0 * (need / bw) / (p['device_s'] / p['calls'])
