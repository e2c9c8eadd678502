"""Steps the window ran: a counter a new cell brings with it."""
LAYER = 'toy layer'
UNIT = 'count'
BETTER = 'higher'
SOURCE = 'program_counter'


def read(run):
    return run['counters']['steps']
