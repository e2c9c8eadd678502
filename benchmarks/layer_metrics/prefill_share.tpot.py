"""The share of device busy time in executions that carried a prefill chunk: the prefill
program's, and those of a program that carried a chunk beside the lanes."""
LAYER = 'model step (serving/paged.py programs)'
UNIT = '%'
BETTER = 'lower'
SOURCE = 'device_trace'


from harness import trace


def read(run):
    t = run['trace']
    if 'prefill' not in t['programs'] or not t['busy_s']:
        return None
    return 100.0 * trace.carried(t['programs'], 'prefill')['device_s'] \
        / t['busy_s']
