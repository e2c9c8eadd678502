"""Median host time of pe.run ending in block_until_ready, over the window's steps."""
LAYER = 'program to step (executor.py, parallel_executor.py)'
UNIT = 'ms'
BETTER = 'lower'
SOURCE = 'host_clock'


def read(run):
    return run['counters'].get('step_ms_p50')
