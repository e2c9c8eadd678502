"""Device time of the flash_attention forward and backward ops over busy time."""
LAYER = 'kernels (pallas/flash_attention.py)'
UNIT = '%'
BETTER = 'lower'
SOURCE = 'device_trace'


FLASH_OPS = ('flash_attention', 'flash_attention_grad')


def read(run):
    t = run['trace']
    flash = sum(t['ops'].get(k, 0.0) for k in FLASH_OPS)
    return 100.0 * flash / t['busy_s'] if flash else None
