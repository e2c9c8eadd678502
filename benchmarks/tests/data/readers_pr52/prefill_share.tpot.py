"""The prefill program's share of device busy time."""
LAYER = 'model step (serving/paged.py programs)'
UNIT = '%'
BETTER = 'lower'
SOURCE = 'device_trace'


def read(run):
    t = run['trace']
    p = t['programs'].get('prefill')
    return 100.0 * p['device_s'] / t['busy_s'] if p and t['busy_s'] else None
