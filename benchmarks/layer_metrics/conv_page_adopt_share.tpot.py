"""Streams that took their conv rows from a cached page (they opened on a resident page
boundary or a registered tail) over streams admitted in the window: the program's
`serving.page_state.streams_adopted` over the streams the benchmark saw opened. The rest
started from zero rows and prefilled their whole prompt."""
LAYER = 'model step (serving/paged.py programs)'
UNIT = '%'
BETTER = 'higher'
SOURCE = 'program_counter'


def read(run):
    c = run['counters']
    if not c.get('streams_opened') or 'page_state_streams_adopted' not in c:
        return None
    return 100.0 * c['page_state_streams_adopted'] / c['streams_opened']
