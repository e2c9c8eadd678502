"""Device time of the sliding layers' decode attention (op paged_window_attention: the Pallas
kernel over each lane's last `sliding_window_size` tokens' pages) over busy time. The full
layer's calls (paged_attention) and the prefill chunks' gathered attention are not in it."""
LAYER = 'kernels (pallas/paged_attention.py)'
UNIT = '%'
BETTER = 'lower'
SOURCE = 'device_trace'


def read(run):
    t = run['trace']
    op_s = t['ops'].get('paged_window_attention', 0.0)
    return 100.0 * op_s / t['busy_s'] if op_s and t['busy_s'] else None
