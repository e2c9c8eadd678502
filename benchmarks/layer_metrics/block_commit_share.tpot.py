"""Share of the block step's lane-passes in the window that were commits (the program's counters
serving.block.commits over serving.block.passes): passes that hand no row a new id and exist to
leave the block's final K/V in the cache. 1 / (T + 1) = 0.2 at T = 4 denoising passes a block."""
LAYER = 'engine (serving/engine.py)'
UNIT = 'share'
BETTER = 'lower'
SOURCE = 'program_counter'


def read(run):
    c = run['counters']
    if not c.get('block_passes'):
        return None
    return c['block_commits'] / c['block_passes']
