"""Set-up phase: process start to the first jax.devices(). The five phases sum to setup_s."""
LAYER = 'set-up (benchmarks/harness)'
UNIT = 's'
BETTER = 'lower'
SOURCE = 'host_clock'


def read(run):
    return run['setup']['init']
