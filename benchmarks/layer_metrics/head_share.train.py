"""Device time of the fused LM head's forward and backward ops over busy time."""
LAYER = 'ops (ops/)'
UNIT = '%'
BETTER = 'lower'
SOURCE = 'device_trace'


HEAD_OPS = ('fused_softmax_cross_entropy', 'fused_softmax_cross_entropy_grad')


def read(run):
    t = run['trace']
    head = sum(t['ops'].get(k, 0.0) for k in HEAD_OPS)
    return 100.0 * head / t['busy_s'] if head else None
