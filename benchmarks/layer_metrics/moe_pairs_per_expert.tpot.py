"""Rows a held expert that was read worked on, a decode step and a layer: the program's
`serving.moe.decode.pairs` over `serving.moe.decode.experts_touched` (counted on the
device by op moe_experts). The deployment's figure at the same lanes a chip is 8 times
this; both read each expert's weights once a step."""
LAYER = 'kernels (ops/moe_ops.py)'
UNIT = 'rows'
BETTER = 'higher'
SOURCE = 'program_counter'


def read(run):
    c = run['counters']
    if not c.get('moe_experts_touched'):
        return None
    return c['moe_pairs'] / c['moe_experts_touched']
