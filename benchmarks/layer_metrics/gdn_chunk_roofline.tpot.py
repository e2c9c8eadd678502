"""The least time the chip could take for the gated_delta_chunk ops of the traced slice
(the larger of their FLOPs over the bf16 peak and their bytes over the HBM peak, for the
prompt tokens they really carried; harness/costs_hybrid) over the ops' device time. The ops
are those of every execution that held one, in whatever program (`op_runs`); tokens a chunk
from the program's `state_tokens` attr of the slice's own steps that carried a chunk
(builders/gpt2.slice_counts)."""
LAYER = 'kernels (ops/delta_rule_ops.py)'
UNIT = '%'
BETTER = 'higher'
SOURCE = 'device_trace'


from harness import costs_hybrid, peaks


def read(run):
    t, c = run['trace'], run['counters']
    op_s = t['ops'].get('gated_delta_chunk', 0.0)
    runs = t['op_runs'].get('gated_delta_chunk')
    chunks = c.get('slice_prefill_calls')
    if not op_s or not runs or not chunks or not c.get('slice_state_tokens'):
        return None
    tokens = c['slice_state_tokens'] / chunks           # mean a chunk
    peak = peaks.peaks_of(run['device']['kind'])
    least = max(
        costs_hybrid.gdn_chunk_flops(run['config'], tokens)
        / peak['bf16_flops'],
        costs_hybrid.gdn_chunk_bytes(run['config'], tokens)
        / peak['hbm_bytes_s'])
    ops = runs * costs_hybrid.kinds(run['config']).count('linear_attention')
    return 100.0 * ops * least / op_s
