"""Host time of `exe.run`, the largest of the window's steps: the host's part of a
step that stalls (PERF.md section 6)."""
LAYER = 'program to step (executor.py, parallel_executor.py)'
UNIT = 'ms'
BETTER = 'lower'
SOURCE = 'program_span'


from harness import spans


def read(run):
    v = spans.of_run(run)['training']
    return max(v['run_ms']) if v else None
