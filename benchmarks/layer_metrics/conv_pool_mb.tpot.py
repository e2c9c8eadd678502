"""Bytes the conv pools' entries of the pages in use hold at the window's end (pages handed
out x K-1 rows x the hidden width x conv layers: the open streams' and what the prefix cache
keeps of finished prompts), MB (1e6 bytes): the program's `serving.page_state.bytes`
gauge."""
LAYER = 'model step (serving/paged.py programs)'
UNIT = 'MB'
BETTER = 'lower'
SOURCE = 'program_counter'


def read(run):
    b = run['counters'].get('page_state_bytes_max')
    return b / 1e6 if b else None
