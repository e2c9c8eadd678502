"""Bytes a decode step of the SmallThinker block has to move (float32 weights outside the
experts once, the untied head among them; the experts its lanes chose, a mean over the
layers; K/V of the live tokens in the global layer and of each lane's window in the sliding
layers; harness/costs_smallthinker.decode_step_bytes: experts a layer and rows a step from
the decode steps of the traced slice's own seconds, builders/smallthinker.py's `slice_*`
counters) over the HBM peak, over the decode program's device time. Memory-bound: one token
a lane."""
LAYER = 'kernels (decode program)'
UNIT = '%'
BETTER = 'higher'
SOURCE = 'device_trace'


from harness import costs_smallthinker as costs, peaks


def read(run):
    p = run['trace']['programs'].get('decode')
    c = run['counters']
    if not p or not p['calls'] or not c.get('slice_moe_layer_calls') \
            or not c.get('slice_decode_calls') \
            or 'slice_window_rows_read' not in c:
        return None
    steps = c['slice_decode_calls']
    need = costs.decode_step_bytes(
        run['config'], c['slice_full_rows_read'] / steps,
        c['slice_window_rows_read'] / steps,
        c['slice_moe_experts_touched'] / c['slice_moe_layer_calls'])
    bw = peaks.peaks_of(run['device']['kind'])['hbm_bytes_s']
    return 100.0 * (need / bw) / (p['device_s'] / p['calls'])
