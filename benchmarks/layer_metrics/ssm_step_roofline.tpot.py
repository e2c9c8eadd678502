"""The least time the chip could take for the ssd_step ops of the traced slice (the larger
of their bytes over the HBM peak, each live lane's state read once and written once, and
their FLOPs over the bf16 peak; harness/costs_nemotron_h) over the ops' device time. The ops
are those of every execution that held one, in whatever program (`op_runs`); lanes a step
from the program's `state_lanes` attr of the slice's own steps that carried lanes
(builders/gpt2.slice_counts), so bytes and seconds are of the same executions.
Memory-bound: 4 FLOP for 8 bytes."""
LAYER = 'kernels (ops/ssd_ops.py)'
UNIT = '%'
BETTER = 'higher'
SOURCE = 'device_trace'


from harness import costs_nemotron_h as costs, peaks


def read(run):
    t, c = run['trace'], run['counters']
    op_s = t['ops'].get('ssd_step', 0.0)
    runs = t['op_runs'].get('ssd_step')
    steps = c.get('slice_decode_calls')
    if not op_s or not runs or not steps or not c.get('slice_state_lanes'):
        return None
    lanes = c['slice_state_lanes'] / steps              # mean a step
    peak = peaks.peaks_of(run['device']['kind'])
    least = max(costs.ssd_step_bytes(run['config'], lanes)
                / peak['hbm_bytes_s'],
                costs.ssd_step_flops(run['config'], lanes)
                / peak['bf16_flops'])
    ops = runs * costs.kinds(run['config']).count('M')
    return 100.0 * ops * least / op_s
