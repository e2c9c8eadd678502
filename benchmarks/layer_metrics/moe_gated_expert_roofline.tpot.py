"""The least time the chip could take for the moe_experts ops of the traced slice where an
expert is three matrices (the larger of the bytes of the held experts their rows chose over
the HBM peak and the pairs' FLOPs over the bf16 peak; harness/costs_axk1) over the ops'
device time. The ops are those of every execution that held one, in whatever program;
experts and pairs a layer from what the expert layers counted in the slice's own seconds,
decode steps and prefill chunks each at their own mean (harness/slices.expert_least)."""
LAYER = 'kernels (ops/moe_ops.py)'
UNIT = '%'
BETTER = 'higher'
SOURCE = 'device_trace'


from harness import costs_axk1 as costs, peaks, slices


def read(run):
    op_s = run['trace']['ops'].get('moe_experts', 0.0)
    if not op_s or 'moe_intermediate_size' not in run['config']:
        return None
    least = slices.expert_least(
        run, costs, costs.layers(run['config'])[1],
        peaks.peaks_of(run['device']['kind']))
    return 100.0 * least / op_s if least else None
