"""Device time per execution of the decode program, from the trace: the executions that
carried lanes and nothing else (one that carried a chunk too goes by its joined name,
harness/trace.reduce_events)."""
LAYER = 'model step (serving/paged.py programs)'
UNIT = 'ms'
BETTER = 'lower'
SOURCE = 'device_trace'


def read(run):
    p = run['trace']['programs'].get('decode')
    return 1e3 * p['device_s'] / p['calls'] if p and p['calls'] else None
