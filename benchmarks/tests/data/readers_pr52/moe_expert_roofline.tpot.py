"""The least time the chip could take for the moe_experts ops of the traced window (the
larger of the bytes of the held experts their rows chose over the HBM peak and the pairs'
FLOPs over the bf16 peak; harness/costs_nemotron_h, experts and pairs a layer from the
program's `serving.moe.*` counters, decode steps and prefill chunks each at their own mean)
over the ops' device time."""
LAYER = 'kernels (ops/moe_ops.py)'
UNIT = '%'
BETTER = 'higher'
SOURCE = 'device_trace'


from harness import costs_nemotron_h as costs, peaks


def read(run):
    t, c = run['trace'], run['counters']
    op_s = t['ops'].get('moe_experts', 0.0)
    if not op_s:
        return None
    peak = peaks.peaks_of(run['device']['kind'])
    layers = costs.kinds(run['config']).count('E')
    least = 0.0
    for program, pre in (('decode', 'moe_'), ('prefill', 'moe_prefill_')):
        p = t['programs'].get(program)
        calls = c.get(pre + 'layer_calls')
        if not p or not p['calls'] or not calls:
            continue
        least += p['calls'] * layers * max(
            costs.expert_bytes(run['config'],
                               c[pre + 'experts_touched'] / calls)
            / peak['hbm_bytes_s'],
            costs.expert_flops(run['config'], c[pre + 'pairs'] / calls)
            / peak['bf16_flops'])
    return 100.0 * least / op_s if least else None
