"""Set-up phase: the fixed warm-up (closed loop: up to the completions that open the window). The five phases sum to setup_s."""
LAYER = 'set-up (benchmarks/harness)'
UNIT = 's'
BETTER = 'lower'
SOURCE = 'host_clock'


def read(run):
    return run['setup']['warm']
