"""Lane-passes of the block step over the tokens delivered in the window (the program's counters
serving.block.passes over serving.block.tokens): what a token costs in passes of its lane. A
block of B tokens takes its denoising passes and one commit, (T + 1) / B = 1.25 at B = T = 4
under the static rules; the first block of a stream, which opens with the prompt's last tokens
fixed, takes fewer passes for fewer tokens, and a budget that ends inside a block drops some. A
later fusion of a block's commit into the next block's first pass would move it to 1."""
LAYER = 'engine (serving/engine.py)'
UNIT = 'passes/token'
BETTER = 'lower'
SOURCE = 'program_counter'


def read(run):
    c = run['counters']
    if not c.get('block_tokens'):
        return None
    return c['block_passes'] / c['block_tokens']
