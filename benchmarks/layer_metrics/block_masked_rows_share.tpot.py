"""Share of the rows the block steps of the window carried that went in still masked (the
program's counters serving.block.masked_rows over serving.block.rows): rows whose logits could
still hand them an id. (4 + 3 + 2 + 1 + 0) / 20 = 0.5 for a block of 4 in 4 denoising passes
and a commit; a stream's first block, which opens with fixed tokens, reads lower."""
LAYER = 'engine (serving/engine.py)'
UNIT = 'share'
BETTER = 'higher'
SOURCE = 'program_counter'


def read(run):
    c = run['counters']
    if not c.get('block_rows'):
        return None
    return c['block_masked_rows'] / c['block_rows']
