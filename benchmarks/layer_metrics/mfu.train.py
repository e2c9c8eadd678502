"""Model FLOPs utilisation: the FLOPs the model needs per token (harness/costs.py:
forward + backward matmuls, causal attention at half, no recomputation) x
train_items_s over chips x the bf16 peak."""
LAYER = 'ops (ops/)'
UNIT = '%'
BETTER = 'higher'
SOURCE = 'host_clock'


from harness import costs, peaks


def read(run):
    if run['device']['platform'] != 'tpu':
        return None
    peak = peaks.peaks_of(run['device']['kind'])['bf16_flops']
    flops = costs.train_flops_per_token(
        run['config'], run['plan']['seq_len'])
    return 100.0 * flops * run['e2e']['train_items_s'] / (run['chips'] * peak)
