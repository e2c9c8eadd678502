"""Bytes the paged_attention ops of the traced window have to read where a page holds 8 K/V
heads (K and V of every live token, once whatever the number of query heads that share them;
harness/costs_granite_h.kv_bytes_per_token; live tokens a step from the decode steps of the
traced slice's own seconds, the least their pages can hold) over the HBM peak, over the ops'
device time. The accepted paged_attn_gqa_roofline takes its tokens as a mean over the whole
window, which this cell's occupancy does not allow: 126 % where this reads 86 % (PR 45)."""
LAYER = 'kernels (pallas/paged_attention.py)'
UNIT = '%'
BETTER = 'higher'
SOURCE = 'device_trace'


from harness import costs_granite_h as costs, peaks


def read(run):
    t, c = run['trace'], run['counters']
    op_s = t['ops'].get('paged_attention', 0.0)
    p = t['programs'].get('decode')
    steps = c.get('slice_decode_calls')
    if not op_s or not p or not p['calls'] or not steps \
            or 'slice_live_tokens' not in c:
        return None
    need = p['calls'] * c['slice_live_tokens'] / steps \
        * costs.kv_bytes_per_token(run['config'])
    bw = peaks.peaks_of(run['device']['kind'])['hbm_bytes_s']
    return 100.0 * (need / bw) / op_s
