"""Required FLOPs of the flash kernels' calls in the traced window (causal, forward
+ backward, the backward's re-run forward not counted) over peak, over their
device time. Compute-bound: at d=128 the kernels' bytes are far under the ridge."""
LAYER = 'kernels (pallas/flash_attention.py)'
UNIT = '%'
BETTER = 'higher'
SOURCE = 'device_trace'


from harness import costs, peaks

FLASH_OPS = ('flash_attention', 'flash_attention_grad')


def read(run):
    t = run['trace']
    flash_s = sum(t['ops'].get(k, 0.0) for k in FLASH_OPS)
    steps = t['span_calls'].get('bench.train_step', 0)   # whole steps
    if not flash_s or not steps:
        return None
    per_chip_seqs = run['plan']['per_step'] / run['chips']
    flops = steps * per_chip_seqs * costs.flash_flops_per_sequence(
        run['config'], run['plan']['seq_len'])
    peak = peaks.peaks_of(run['device']['kind'])['bf16_flops']
    return 100.0 * flops / peak / flash_s
