"""Share of the traced window in which the first chip was idle between two executions while
the host was preparing or dispatching the next program: in a `*.tables` span, `exe.feed`,
`exe.prepare`, the rest of `exe.run` or a `device_segment:*` (what `idle_feed_share.tpot`
reads, less the gaps inside a program and the evictions: `harness/idle_account.py`)."""
LAYER = 'device'
UNIT = '%'
BETTER = 'lower'
SOURCE = 'device_trace'


from harness import idle_account


def read(run):
    return idle_account.device_share(run, 'dispatch')
