"""Bytes a decode step of the LFM2 block has to move (float32 weights outside the routed
experts once, the experts its lanes chose, K/V of the live tokens in the attention layers at
64-wide rows, the live lanes' conv rows read and written;
harness/costs_lfm2.decode_step_bytes: tokens and lanes a step means over the traced slice's
steps that carried lanes and no chunk, experts a layer from what the expert layers counted
for decode steps in the slice's seconds; builders/gpt2.slice_counts and
_StepProbe.counters) over the HBM peak, over the decode program's device time in the same
slice. Memory-bound: one token a lane."""
LAYER = 'kernels (decode program)'
UNIT = '%'
BETTER = 'higher'
SOURCE = 'device_trace'


from harness import costs_lfm2 as costs, peaks


def read(run):
    p = run['trace']['programs'].get('decode')
    c = run['counters']
    steps = c.get('slice_plain_decode_calls')
    if not p or not p['calls'] or not steps \
            or not c.get('slice_moe_layer_calls'):
        return None
    need = costs.decode_step_bytes(
        run['config'], c['slice_plain_live_tokens'] / steps,
        c['slice_plain_lanes'] / steps,
        c['slice_moe_experts_touched'] / c['slice_moe_layer_calls'])
    bw = peaks.peaks_of(run['device']['kind'])['hbm_bytes_s']
    return 100.0 * (need / bw) / (p['device_s'] / p['calls'])
