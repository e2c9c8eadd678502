"""Segments the executor compiled inside the window (jit_cache_stats()['compiled_segments'],
after minus before). 0."""
LAYER = 'program to step (executor.py, parallel_executor.py)'
UNIT = 'count'
BETTER = 'lower'
SOURCE = 'program_counter'


def read(run):
    return run['counters'].get('compiles_in_window')
