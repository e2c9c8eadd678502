"""95th percentile of submit time minus due time over the judged requests: how late the generator ran."""
LAYER = 'load generator (benchmarks/harness)'
UNIT = 'ms'
BETTER = 'lower'
SOURCE = 'host_clock'


def read(run):
    return run['counters'].get('gen_late_p95_ms')
