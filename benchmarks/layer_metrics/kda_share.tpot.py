"""Device time of the ops of the delta rule with a decay a key channel (kda_step, kda_chunk,
and the short_conv in front of them) over busy time. The mixers' projections and gates are
plain matmuls and are not in it."""
LAYER = 'kernels (ops/delta_rule_ops.py)'
UNIT = '%'
BETTER = 'lower'
SOURCE = 'device_trace'


KDA_OPS = ('kda_step', 'kda_chunk', 'short_conv')


def read(run):
    t = run['trace']
    kda = sum(t['ops'].get(k, 0.0) for k in KDA_OPS)
    return 100.0 * kda / t['busy_s'] if kda and t['busy_s'] else None
