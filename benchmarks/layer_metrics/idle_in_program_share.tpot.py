"""Share of the traced window in which the first chip was idle INSIDE one execution of the
`XLA Modules` line (between two ops of one program: an awaited slice, a copy), whatever
the host was doing: the device's own, and the first test of the partition in
`harness/idle_account.py`, because the pipelined loop keeps the host a pass ahead."""
LAYER = 'device'
UNIT = '%'
BETTER = 'lower'
SOURCE = 'device_trace'


from harness import idle_account


def read(run):
    return idle_account.device_share(run, 'in_program')
