"""Set-up phase: weights on the device (serving: save, load, prepare_decoding, engine start). The five phases sum to setup_s."""
LAYER = 'set-up (benchmarks/harness)'
UNIT = 's'
BETTER = 'lower'
SOURCE = 'host_clock'


def read(run):
    return run['setup']['weights']
