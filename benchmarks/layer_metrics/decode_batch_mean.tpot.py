"""Lanes fed per decode step: the serving.decode_batch histogram's sum over its count, in the window."""
LAYER = 'engine (serving/engine.py)'
UNIT = 'count'
BETTER = 'higher'
SOURCE = 'program_counter'


def read(run):
    c = run['counters']
    n = c.get('decode_batch_count')
    return c['decode_batch_sum'] / n if n else None
