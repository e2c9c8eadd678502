"""Device time of the state-space mixer's own ops (ssd_step, ssd_chunk, short_conv) over
busy time."""
LAYER = 'kernels (ops/ssd_ops.py)'
UNIT = '%'
BETTER = 'lower'
SOURCE = 'device_trace'


SSM_OPS = ('ssd_step', 'ssd_chunk', 'short_conv')


def read(run):
    t = run['trace']
    ssm = sum(t['ops'].get(k, 0.0) for k in SSM_OPS)
    return 100.0 * ssm / t['busy_s'] if ssm and t['busy_s'] else None
