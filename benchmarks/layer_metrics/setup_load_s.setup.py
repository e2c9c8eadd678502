"""Set-up phase: the first call of each program: the cache load, or the compile. The five phases sum to setup_s."""
LAYER = 'set-up (benchmarks/harness)'
UNIT = 's'
BETTER = 'lower'
SOURCE = 'host_clock'


def read(run):
    return run['setup']['load']
