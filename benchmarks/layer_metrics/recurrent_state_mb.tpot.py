"""Bytes the recurrent state holds on the device (every slot's delta state and
convolution rows, every linear_attention layer), MB (1e6 bytes): the program's
`serving.recurrent_state_bytes` gauge. State that is not pages: what `kv_pages_in_use`
does not count."""
LAYER = 'model step (serving/paged.py programs)'
UNIT = 'MB'
BETTER = 'lower'
SOURCE = 'program_counter'


def read(run):
    b = run['counters'].get('recurrent_state_bytes_max')
    return b / 1e6 if b else None
