"""The least time the chip could take for the moe_experts ops of the traced slice where the
router is 320 wide and 10 experts of three matrices are held: the larger of the bytes of the
held experts the rows chose over the HBM peak and the pairs' FLOPs over the bf16 peak
(harness/costs_solar2), for the ops of every execution that held one, in whatever program,
experts and pairs a layer from what the expert sublayers counted in the slice's own seconds,
decode steps and prefill chunks each at their own mean (harness/slices.expert_least), over
the ops' device time PLUS that of the program's asynchronous fetches (`hlo:slice-start` /
`-done`, `hlo:copy-start` / `-done`: they carry no op's name, so the op's own time leaves out
the wait for its weights; some feed other ops, so the share reads a few points low, never
high: moe_gated_expert_fetch_roofline.tpot's reasoning). The accepted expert rooflines read
another configuration's costs (costs_granite_h takes intermediate_size for an expert's width:
here a dense layer's 10240) or the op's time alone (109 % on the Granite cell)."""
LAYER = 'kernels (ops/moe_ops.py)'
UNIT = '%'
BETTER = 'higher'
SOURCE = 'device_trace'


from harness import costs_solar2 as costs, peaks, slices


def read(run):
    t = run['trace']
    op_s = t['ops'].get('moe_experts', 0.0)
    if not op_s or 'gqa_layers' not in run['config']:
        return None
    op_s += sum(t['ops'].get('hlo:' + kind + phase, 0.0)
                for kind in ('slice', 'copy') for phase in ('-start', '-done'))
    least = slices.expert_least(
        run, costs, len(costs.kinds(run['config'])),
        peaks.peaks_of(run['device']['kind']))
    return 100.0 * least / op_s if least else None
