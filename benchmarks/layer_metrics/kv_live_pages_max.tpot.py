"""The most pages that open streams alone held after any step of the window (each
slot's tokens, `slot_tokens()`, in pages of `page_tokens`): what the traffic needs of
the reservation that `peak_hbm_gb` counts whole."""
LAYER = 'model step (serving/paged.py programs)'
UNIT = 'count'
BETTER = 'lower'
SOURCE = 'program_counter'


def read(run):
    return run['counters'].get('kv_live_pages_max')
