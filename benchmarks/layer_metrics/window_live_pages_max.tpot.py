"""The most pages of the window pool that open streams' tables of the sliding layers held
after any step of the window (`pool_stats()['window_pages_live']`, read by
builders/smallthinker.py after every chunk and step): what the traffic needs of the
window pool's reservation, beside `kv_live_pages_max` for the full layer's."""
LAYER = 'cache (serving/paging.py)'
UNIT = 'count'
BETTER = 'lower'
SOURCE = 'program_counter'


def read(run):
    return run['counters'].get('window_live_pages_max')
