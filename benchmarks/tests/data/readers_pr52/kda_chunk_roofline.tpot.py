"""The least time the chip could take for the kda_chunk ops of the traced slice (the larger of
their FLOPs over the bf16 peak and their bytes over the HBM peak, for the prompt tokens they
really carried; harness/costs_solar2) over the ops' device time. Tokens a chunk from the
prefill chunks of the slice's own seconds (the builder's `slice_chunk_tokens` over
`slice_prefill_calls`): bytes, operations and seconds of the same executions."""
LAYER = 'kernels (ops/delta_rule_ops.py)'
UNIT = '%'
BETTER = 'higher'
SOURCE = 'device_trace'


from harness import costs_solar2 as costs, peaks


def read(run):
    t, c = run['trace'], run['counters']
    op_s = t['ops'].get('kda_chunk', 0.0)
    p = t['programs'].get('prefill')
    chunks = c.get('slice_prefill_calls')
    if not op_s or not p or not p['calls'] or not chunks \
            or not c.get('slice_chunk_tokens'):
        return None
    tokens = c['slice_chunk_tokens'] / chunks           # mean a chunk
    peak = peaks.peaks_of(run['device']['kind'])
    least = max(
        costs.kda_chunk_flops(run['config'], tokens) / peak['bf16_flops'],
        costs.kda_chunk_bytes(run['config'], tokens) / peak['hbm_bytes_s'])
    ops = p['calls'] * costs.kinds(run['config']).count('kda')
    return 100.0 * ops * least / op_s
