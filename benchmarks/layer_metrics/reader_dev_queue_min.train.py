"""Minimum of the reader.device_queue_depth gauge, read before every step: 0 means a step waited for input."""
LAYER = 'input (reader/pipeline.py)'
UNIT = 'count'
BETTER = 'higher'
SOURCE = 'program_counter'


def read(run):
    return run['counters'].get('reader_dev_queue_min')
