"""Time in collectives during which no other op runs on that chip, over the traced window."""
LAYER = 'collectives (GSPMD all-reduce)'
UNIT = '%'
BETTER = 'lower'
SOURCE = 'device_trace'


def read(run):
    t = run['trace']
    if run['chips'] < 2:
        return None
    return 100.0 * t['collective_exposed_s'] / t['window_s']
