"""The least time the chip could take for the paged_latent_prefill ops of the traced slice
in the absorbed form that was kept (the larger of a chunk's FLOPs over the bf16 peak and
its context's latent rows over the HBM peak; harness/costs_axk1: live rows a chunk from the
step probe, the context a chunk attends the mean prompt of the plan's judged requests, which
is the same multiset in every seed) over the ops' device time."""
LAYER = 'kernels (ops/latent_attention_ops.py)'
UNIT = '%'
BETTER = 'higher'
SOURCE = 'device_trace'


from harness import costs_axk1 as costs, peaks


def read(run):
    t, c = run['trace'], run['counters']
    op_s = t['ops'].get('paged_latent_prefill', 0.0)
    p = t['programs'].get('prefill')
    if not op_s or not p or not p['calls'] or not c.get('prefill_calls'):
        return None
    reqs = run['plan']['requests'][:run['plan']['judged']]
    context = sum(len(r['prompt']) for r in reqs) / len(reqs)
    rows = c['prefill_tokens'] / c['prefill_calls']
    peak = peaks.peaks_of(run['device']['kind'])
    m = run['config']
    least = max(costs.mla_prefill_flops(m, rows, context) / peak['bf16_flops'],
                costs.mla_prefill_bytes(m, context) / peak['hbm_bytes_s'])
    return 100.0 * p['calls'] * int(m['num_hidden_layers']) * least / op_s
