"""Bytes a block step of the SDAR block has to move (float32 weights outside the experts once, the
untied head among them; the held experts its rows chose, a mean over the layers; K/V of the live
tokens of its lanes in every layer; harness/costs_sdar.block_step_bytes: tokens a step means over
the traced slice's steps that carried lanes and no chunk, experts a layer from what the expert
sublayers counted for block steps in the slice's seconds) over the HBM peak, over the block
program's device time in the same slice: the share of the whole step. Memory-bound: 4 rows a lane."""
LAYER = 'kernels (decode program)'
UNIT = '%'
BETTER = 'higher'
SOURCE = 'device_trace'


from harness import peaks


def read(run):
    try:
        from harness import costs_sdar as costs
    except ImportError:
        return None
    p = run['trace']['programs'].get('decode')
    c = run['counters']
    steps = c.get('slice_plain_decode_calls')
    if not p or not p['calls'] or not steps \
            or not c.get('slice_moe_layer_calls') \
            or 'block_passes' not in c:
        return None
    need = costs.block_step_bytes(
        run['config'], c['slice_plain_live_tokens'] / steps,
        c['slice_moe_experts_touched'] / c['slice_moe_layer_calls'])
    bw = peaks.peaks_of(run['device']['kind'])['hbm_bytes_s']
    return 100.0 * (need / bw) / (p['device_s'] / p['calls'])
