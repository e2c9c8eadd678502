"""Bytes the paged_block_attention ops of the traced slice have to read (K and V of every live
token of a pass's lanes, the block's own rows among them, ONCE a lane a pass whatever the 32
query heads and 4 block rows that share them; harness/costs_sdar.block_attention_bytes) over the
HBM peak, over the ops' device time. The ops are those of every execution that held one
(`op_runs`); rows a pass from the lanes' live tokens in the slice's own steps that carried lanes
(the builder's probe, from the `live_tokens` attr of their `paged.decode.tables` spans)."""
LAYER = 'kernels (pallas/paged_attention.py)'
UNIT = '%'
BETTER = 'higher'
SOURCE = 'device_trace'


from harness import peaks


def read(run):
    try:
        from harness import costs_sdar as costs
    except ImportError:
        return None
    t, c = run['trace'], run['counters']
    op_s = t['ops'].get('paged_block_attention', 0.0)
    runs = t['op_runs'].get('paged_block_attention')
    steps = c.get('slice_decode_calls')
    if not op_s or not runs or not steps or not c.get('slice_live_tokens'):
        return None
    need = runs * int(run['config']['num_hidden_layers']) \
        * costs.block_attention_bytes(run['config'],
                                      c['slice_live_tokens'] / steps)
    bw = peaks.peaks_of(run['device']['kind'])['hbm_bytes_s']
    return 100.0 * (need / bw) / op_s
