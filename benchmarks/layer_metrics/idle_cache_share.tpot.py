"""Share of the traced window in which the first chip was idle between two executions while
the host was in the prefix cache's scans: `pt.paged.prefix.evict` (under the `*.tables`
span that asked for the page) or `pt.paged.prefix.register` (`harness/idle_account.py`).
None for a program without them."""
LAYER = 'device'
UNIT = '%'
BETTER = 'lower'
SOURCE = 'device_trace'


from harness import idle_account


def read(run):
    return idle_account.device_share(run, 'cache')
