"""Streams that opened on a snapshot of the recurrent state over streams admitted in the
window: the program's `serving.state.snapshots_adopted` over the streams the benchmark saw
opened. The rest started from zero state and prefilled their whole prompt."""
LAYER = 'model step (serving/paged.py programs)'
UNIT = '%'
BETTER = 'higher'
SOURCE = 'program_counter'


def read(run):
    c = run['counters']
    if not c.get('streams_opened') or 'snapshots_adopted' not in c:
        return None
    return 100.0 * c['snapshots_adopted'] / c['streams_opened']
