"""Bytes the snapshot rows hold on the device (every row: one slot's recurrent state at a
prefix boundary), MB (1e6 bytes): the program's `serving.state.snapshot_bytes` gauge. What
a prefix costs beside its pages."""
LAYER = 'model step (serving/paged.py programs)'
UNIT = 'MB'
BETTER = 'lower'
SOURCE = 'program_counter'


def read(run):
    b = run['counters'].get('state_snapshot_bytes_max')
    return b / 1e6 if b else None
