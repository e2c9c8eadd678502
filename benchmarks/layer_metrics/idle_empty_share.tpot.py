"""Share of the traced window in which the first chip was idle between two executions while
the worker sat in `pt.serve.idle` (no lane, an empty queue): the traffic's idle time, not
the loop's (`harness/idle_account.py`). None for a program without the span."""
LAYER = 'device'
UNIT = '%'
BETTER = 'lower'
SOURCE = 'device_trace'


from harness import idle_account


def read(run):
    return idle_account.device_share(run, 'empty')
