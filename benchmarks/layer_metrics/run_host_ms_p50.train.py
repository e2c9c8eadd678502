"""Host time of `exe.run` (feed, reader pop, prepared-program lookup, dispatch; it
returns device arrays, so no wait for the device), median of the window's steps."""
LAYER = 'program to step (executor.py, parallel_executor.py)'
UNIT = 'ms'
BETTER = 'lower'
SOURCE = 'program_span'


from harness import spans


def read(run):
    v = spans.of_run(run)['training']
    return spans.percentile(v['run_ms'], 0.50) if v else None
