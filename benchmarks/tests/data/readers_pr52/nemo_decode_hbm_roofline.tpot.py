"""Bytes a decode step of the Nemotron-H block has to move (float32 weights outside the
routed experts once, the held experts its lanes chose, K/V of the live tokens in the
attention layers, the live lanes' state read and written;
harness/costs_nemotron_h.decode_step_bytes, each a mean over the window's decode steps)
over the HBM peak, over the decode program's device time. Memory-bound: one token a lane."""
LAYER = 'kernels (decode program)'
UNIT = '%'
BETTER = 'higher'
SOURCE = 'device_trace'


from harness import costs_nemotron_h as costs, peaks


def read(run):
    p = run['trace']['programs'].get('decode')
    c = run['counters']
    if not p or not p['calls'] or not c.get('decode_calls') \
            or not c.get('moe_layer_calls') or 'state_lanes' not in c:
        return None
    need = costs.decode_step_bytes(
        run['config'], c['live_tokens'] / c['decode_calls'],
        c['state_lanes'] / c['decode_calls'],
        c['moe_experts_touched'] / c['moe_layer_calls'])
    bw = peaks.peaks_of(run['device']['kind'])['hbm_bytes_s']
    return 100.0 * (need / bw) / (p['device_s'] / p['calls'])
