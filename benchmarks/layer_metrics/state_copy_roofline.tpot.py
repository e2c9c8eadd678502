"""Bytes the state_row_copy ops of the traced slice have to move (a snapshot or an adoption:
one slot's recurrent state read and written once, harness/costs_granite_h.state_copy_bytes,
times the executions in the trace that held the op) over the HBM peak, over the ops' device
time."""
LAYER = 'model step (serving/paged.py programs)'
UNIT = '%'
BETTER = 'higher'
SOURCE = 'device_trace'


from harness import costs_granite_h as costs, peaks


def read(run):
    t = run['trace']
    op_s = t['ops'].get('state_row_copy', 0.0)
    runs = t['op_runs'].get('state_row_copy')
    if not op_s or not runs:
        return None
    need = runs * costs.state_copy_bytes(run['config'])
    bw = peaks.peaks_of(run['device']['kind'])['hbm_bytes_s']
    return 100.0 * (need / bw) / op_s
