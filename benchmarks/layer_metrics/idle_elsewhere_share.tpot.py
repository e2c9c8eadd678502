"""Share of the traced window in which the device was idle and the host was in any
other program span (scheduling, bookkeeping, packing) or in none."""
LAYER = 'device'
UNIT = '%'
BETTER = 'lower'
SOURCE = 'device_trace'


from harness import spans


def read(run):
    v = spans.of_run(run)['idle']
    return v['elsewhere'] if v else None
