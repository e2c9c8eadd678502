"""Set-up phase: building the programs. The five phases sum to setup_s."""
LAYER = 'set-up (benchmarks/harness)'
UNIT = 's'
BETTER = 'lower'
SOURCE = 'host_clock'


def read(run):
    return run['setup']['build']
