"""1 - the union of device op intervals over the traced window, mean over chips."""
LAYER = 'device'
UNIT = '%'
BETTER = 'lower'
SOURCE = 'device_trace'


def read(run):
    t = run['trace']
    return 100.0 * (1.0 - t['busy_s'] / t['window_s'])
