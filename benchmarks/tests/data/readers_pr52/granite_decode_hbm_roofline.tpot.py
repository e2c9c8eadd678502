"""Bytes a decode step of the GraniteMoeHybrid block has to move (float32 weights outside the
routed experts once, the held experts its lanes chose, K/V of the live tokens in the
attention layer, the live lanes' state read and written;
harness/costs_granite_h.decode_step_bytes: experts a layer, lanes and tokens a step all from
the decode steps of the traced slice's own seconds, builders/granite_h.py's `slice_*`
counters) over the HBM peak, over the decode program's device time. Memory-bound: one token a
lane."""
LAYER = 'kernels (decode program)'
UNIT = '%'
BETTER = 'higher'
SOURCE = 'device_trace'


from harness import costs_granite_h as costs, peaks


def read(run):
    p = run['trace']['programs'].get('decode')
    c = run['counters']
    if not p or not p['calls'] or not c.get('slice_moe_layer_calls') \
            or not c.get('slice_decode_calls') \
            or 'slice_state_lanes' not in c:
        return None
    steps = c['slice_decode_calls']
    need = costs.decode_step_bytes(
        run['config'], c['slice_live_tokens'] / steps,
        c['slice_state_lanes'] / steps,
        c['slice_moe_experts_touched'] / c['slice_moe_layer_calls'])
    bw = peaks.peaks_of(run['device']['kind'])['hbm_bytes_s']
    return 100.0 * (need / bw) / (p['device_s'] / p['calls'])
