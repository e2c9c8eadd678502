"""Pages the sliding layers' page tables gave up in the window because they lay wholly
behind their streams' windows: the program's `serving.window_pages_freed` (counted where a
chunk or a step is booked, serving/paged.py). With one table for all layers these pages
would be held to a stream's end."""
LAYER = 'cache (serving/paging.py)'
UNIT = 'count'
BETTER = 'higher'
SOURCE = 'program_counter'


def read(run):
    return run['counters'].get('window_pages_freed')
