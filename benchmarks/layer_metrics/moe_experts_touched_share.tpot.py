"""Held experts that at least one lane chose, a decode step and a layer, over the experts
held: the program's `serving.moe.decode.experts_touched` over `serving.moe.decode.layer_calls`
times `n_routed_experts`. Near 100 % a step's expert bytes do not depend on where the
seed's weights send the seed's tokens."""
LAYER = 'kernels (ops/moe_ops.py)'
UNIT = '%'
BETTER = 'higher'
SOURCE = 'program_counter'


def read(run):
    c = run['counters']
    if not c.get('moe_layer_calls'):
        return None
    return 100.0 * c['moe_experts_touched'] / (
        c['moe_layer_calls'] * int(run['config']['n_routed_experts']))
