"""Median of the `chunk` gaps of the judged requests with exactly one prefill chunk in
front of their step (`gap_chunks` 1, `gap_sync` 0): a decode step and another request's
chunk. 0 where the window holds no such gap (a rehearsal; never 45 s on the chip)."""
LAYER = 'engine (serving/engine.py)'
UNIT = 'ms'
BETTER = 'lower'
SOURCE = 'program_span'


from harness import gaps


def read(run):
    return gaps.median(run, 'chunk', lambda gap: gap[1] == 1)
