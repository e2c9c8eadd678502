"""Device time of the short convolution's ops (short_conv in its paged forms: a chunk's and a
step's gathers, products and scatters through the page table) over busy time. The gates and
the projections around it are plain elementwise ops and matmuls and are not in it."""
LAYER = 'kernels (ops/delta_rule_ops.py)'
UNIT = '%'
BETTER = 'lower'
SOURCE = 'device_trace'


def read(run):
    t = run['trace']
    conv = t['ops'].get('short_conv', 0.0)
    return 100.0 * conv / t['busy_s'] if conv and t['busy_s'] else None
