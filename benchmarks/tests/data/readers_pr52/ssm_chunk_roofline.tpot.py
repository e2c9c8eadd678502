"""The least time the chip could take for the ssd_chunk ops of the traced window (the larger
of their FLOPs over the bf16 peak and their bytes over the HBM peak, for the prompt tokens
they really carried; harness/costs_nemotron_h) over the ops' device time."""
LAYER = 'kernels (ops/ssd_ops.py)'
UNIT = '%'
BETTER = 'higher'
SOURCE = 'device_trace'


from harness import costs_nemotron_h as costs, peaks


def read(run):
    t, c = run['trace'], run['counters']
    op_s = t['ops'].get('ssd_chunk', 0.0)
    p = t['programs'].get('prefill')
    if not op_s or not p or not p['calls'] or not c.get('prefill_calls'):
        return None
    tokens = c['prefill_tokens'] / c['prefill_calls']  # mean a chunk
    peak = peaks.peaks_of(run['device']['kind'])
    least = max(costs.ssd_chunk_flops(run['config'], tokens)
                / peak['bf16_flops'],
                costs.ssd_chunk_bytes(run['config'], tokens)
                / peak['hbm_bytes_s'])
    ops = p['calls'] * costs.kinds(run['config']).count('M')
    return 100.0 * ops * least / op_s
