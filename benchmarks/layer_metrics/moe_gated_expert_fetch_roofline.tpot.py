"""The least time the chip could take for the moe_experts ops of the traced slice where an
expert is three matrices and XLA fetches their weights ahead of the op, asynchronously: the
larger of the bytes of the held experts the rows chose over the HBM peak and the pairs' FLOPs
over the bf16 peak (harness/costs_granite_h), for the ops of every execution that held one,
in whatever program, experts and pairs a layer from what the expert sublayers counted in the
slice's own seconds, decode steps and prefill chunks each at their own mean
(harness/slices.expert_least), over the ops' device time PLUS that of the program's
asynchronous fetches (`hlo:slice-start` / `-done`, `hlo:copy-start` / `-done`: they carry no
op's name, so the op's own time leaves out the wait for its weights).
tools/async_slices.py follows each fetch to the op that reads it: of 0.375 s in a traced
slice 0.319 s feed moe_experts (slices of three experts' matrices, a layer's whole matrix by
copy), the rest the projections', ssd_step's and short_conv's operands, which the reduction
cannot tell apart and this charges too: the share reads up to 4 points low, never high
(PERF.md section 6, PR 45). moe_gated_expert_roofline takes the op's time alone: 109 % here
with the window's mean counts (PR 45)."""
LAYER = 'kernels (ops/moe_ops.py)'
UNIT = '%'
BETTER = 'higher'
SOURCE = 'device_trace'


from harness import costs_granite_h as costs, peaks, slices


def read(run):
    t = run['trace']
    op_s = t['ops'].get('moe_experts', 0.0)
    if not op_s:
        return None
    op_s += sum(t['ops'].get('hlo:' + kind + phase, 0.0)
                for kind in ('slice', 'copy') for phase in ('-start', '-done'))
    least = slices.expert_least(
        run, costs, len(costs.kinds(run['config'])),
        peaks.peaks_of(run['device']['kind']))
    return 100.0 * least / op_s if least else None
