"""Device time of the served expert op (moe_experts: routing, the held experts' products,
the combine) over busy time. The latent projections and the shared expert are plain
matmuls and are not in it."""
LAYER = 'kernels (ops/moe_ops.py)'
UNIT = '%'
BETTER = 'lower'
SOURCE = 'device_trace'


def read(run):
    t = run['trace']
    moe = t['ops'].get('moe_experts', 0.0)
    return 100.0 * moe / t['busy_s'] if moe and t['busy_s'] else None
