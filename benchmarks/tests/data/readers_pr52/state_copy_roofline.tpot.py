"""Bytes the state copy programs of the traced window have to move (a snapshot or an adoption:
one slot's recurrent state read and written once, harness/costs_granite_h.state_copy_bytes,
times the programs' executions in the trace) over the HBM peak, over the device time of
their state_row_copy ops."""
LAYER = 'model step (serving/paged.py programs)'
UNIT = '%'
BETTER = 'higher'
SOURCE = 'device_trace'


from harness import costs_granite_h as costs, peaks


def read(run):
    t = run['trace']
    op_s = t['ops'].get('state_row_copy', 0.0)
    p = t['programs'].get('state_copy')
    if not op_s or not p or not p['calls']:
        return None
    need = p['calls'] * costs.state_copy_bytes(run['config'])
    bw = peaks.peaks_of(run['device']['kind'])['hbm_bytes_s']
    return 100.0 * (need / bw) / op_s
