"""The least time the chip could take for the paged_latent_prefill ops of the traced slice
in the absorbed form that was kept (the larger of a chunk's FLOPs over the bf16 peak and
its context's latent rows over the HBM peak; harness/costs_axk1) over the ops' device time.
The ops are those of every execution that held one, in whatever program (`op_runs`); live
rows a chunk from the step probe, over the slice's own steps that carried a chunk (the
program's span gives a chunk's tokens only where there is recurrent state:
builders/gpt2.slice_counts' `slice_prefill_tokens`); the context a chunk attends is the mean
prompt of the plan's judged requests, which is the same multiset in every seed."""
LAYER = 'kernels (ops/latent_attention_ops.py)'
UNIT = '%'
BETTER = 'higher'
SOURCE = 'device_trace'


from harness import costs_axk1 as costs, peaks


def read(run):
    t, c = run['trace'], run['counters']
    op_s = t['ops'].get('paged_latent_prefill', 0.0)
    runs = t['op_runs'].get('paged_latent_prefill')
    chunks = c.get('slice_prefill_calls')
    if not op_s or not runs or not chunks \
            or not c.get('slice_prefill_tokens'):
        return None
    reqs = run['plan']['requests'][:run['plan']['judged']]
    context = sum(len(r['prompt']) for r in reqs) / len(reqs)
    rows = c['slice_prefill_tokens'] / chunks
    peak = peaks.peaks_of(run['device']['kind'])
    m = run['config']
    least = max(costs.mla_prefill_flops(m, rows, context) / peak['bf16_flops'],
                costs.mla_prefill_bytes(m, context) / peak['hbm_bytes_s'])
    return 100.0 * runs * int(m['num_hidden_layers']) * least / op_s
