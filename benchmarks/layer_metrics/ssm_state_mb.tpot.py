"""Bytes the state-space state holds on the device (every slot's state and convolution
rows, every mamba layer), MB (1e6 bytes): the program's `serving.ssm.state_bytes` gauge.
State that is not pages: what `kv_pages_in_use` does not count."""
LAYER = 'model step (serving/paged.py programs)'
UNIT = 'MB'
BETTER = 'lower'
SOURCE = 'program_counter'


def read(run):
    b = run['counters'].get('ssm_state_bytes_max')
    return b / 1e6 if b else None
