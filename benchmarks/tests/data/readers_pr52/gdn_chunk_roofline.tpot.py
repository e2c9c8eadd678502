"""The least time the chip could take for the gated_delta_chunk ops of the traced window
(the larger of their FLOPs over the bf16 peak and their bytes over the HBM peak, for the
prompt tokens they really carried; harness/costs_hybrid) over the ops' device time."""
LAYER = 'kernels (ops/delta_rule_ops.py)'
UNIT = '%'
BETTER = 'higher'
SOURCE = 'device_trace'


from harness import costs_hybrid, peaks


def read(run):
    t, c = run['trace'], run['counters']
    op_s = t['ops'].get('gated_delta_chunk', 0.0)
    p = t['programs'].get('prefill')
    if not op_s or not p or not p['calls'] or not c.get('prefill_calls'):
        return None
    tokens = c['prefill_tokens'] / c['prefill_calls']  # mean a chunk
    peak = peaks.peaks_of(run['device']['kind'])
    least = max(
        costs_hybrid.gdn_chunk_flops(run['config'], tokens)
        / peak['bf16_flops'],
        costs_hybrid.gdn_chunk_bytes(run['config'], tokens)
        / peak['hbm_bytes_s'])
    ops = p['calls'] * costs_hybrid.kinds(run['config']).count(
        'linear_attention')
    return 100.0 * ops * least / op_s
