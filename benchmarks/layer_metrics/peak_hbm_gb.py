"""device.memory_stats()['peak_bytes_in_use'] on the fullest chip, GB (1e9 bytes), before the
reference runs. On this runtime it reads the arrays held (weights, optimizer state, the
page pool), not a program's temporaries: PERF.md section 7."""
LAYER = 'device'
UNIT = 'GB'
BETTER = 'lower'
SOURCE = 'program_counter'


def read(run):
    return run['device']['memory_peak_bytes'] / 1e9
