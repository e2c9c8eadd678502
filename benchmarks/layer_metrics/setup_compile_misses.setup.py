"""Backend compiles during set-up that JAX's persistent cache did not serve
(harness/setup_clock.CompileMisses). 0 in every run but a checkout's first."""
LAYER = 'set-up (benchmarks/harness)'
UNIT = 'count'
BETTER = 'lower'
SOURCE = 'program_counter'


def read(run):
    return run['counters'].get('setup_compile_misses')
