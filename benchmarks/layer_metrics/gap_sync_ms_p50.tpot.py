"""Median of the `sync` gaps (`gap_sync` 1: the step was dispatched with nothing in
flight) of requests that were already decoding; a request's own first gap, behind its own
last chunk, is left out. A chunk's synchronous wait, the host's section and a whole step.
0 where the window holds no such gap (a rehearsal; never 45 s on the chip)."""
LAYER = 'engine (serving/engine.py)'
UNIT = 'ms'
BETTER = 'lower'
SOURCE = 'program_span'


from harness import gaps


def read(run):
    return gaps.median(run, 'sync', lambda gap: not gap[3])
