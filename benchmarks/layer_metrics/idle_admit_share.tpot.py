"""Share of the traced window in which the first chip was idle between two executions while
the host admitted a request: in `pt.serve.admit`, `pt.paged.open` or
`pt.paged.prefix.match` (`harness/idle_account.py`). None for a program without them."""
LAYER = 'device'
UNIT = '%'
BETTER = 'lower'
SOURCE = 'device_trace'


from harness import idle_account


def read(run):
    return idle_account.device_share(run, 'admit')
