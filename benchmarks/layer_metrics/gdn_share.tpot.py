"""Device time of the delta rule's ops (gated_delta_step, gated_delta_chunk, short_conv)
over busy time."""
LAYER = 'kernels (ops/delta_rule_ops.py)'
UNIT = '%'
BETTER = 'lower'
SOURCE = 'device_trace'


GDN_OPS = ('gated_delta_step', 'gated_delta_chunk', 'short_conv')


def read(run):
    t = run['trace']
    gdn = sum(t['ops'].get(k, 0.0) for k in GDN_OPS)
    return 100.0 * gdn / t['busy_s'] if gdn and t['busy_s'] else None
