"""Mean host time of one decode_step call (the wrapper on the decoder instance), in the window."""
LAYER = 'engine (serving/engine.py)'
UNIT = 'ms'
BETTER = 'lower'
SOURCE = 'host_clock'


def read(run):
    c = run['counters']
    n = c.get('decode_calls')
    return 1e3 * c['decode_s'] / n if n else None
