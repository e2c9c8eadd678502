"""Bytes the latent pages in use hold at the window's end (pages handed out x 16 tokens x
the stored row x layers: the cached documents, the open streams and what the prefix cache
keeps of finished prompts), MB (1e6 bytes): the program's `serving.latent.cache_bytes`
gauge."""
LAYER = 'model step (serving/paged.py programs)'
UNIT = 'MB'
BETTER = 'lower'
SOURCE = 'program_counter'


def read(run):
    b = run['counters'].get('latent_cache_bytes_max')
    return b / 1e6 if b else None
