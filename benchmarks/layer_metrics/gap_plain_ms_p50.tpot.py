"""Median of the `plain` gaps of the judged requests over the whole window: a token whose
step was dispatched with the step before in flight and no prefill chunk in front
(`gap_chunks` 0, `gap_sync` 0 on its `serve.decode` span). Where the device sets the pace
it is the decode program at the lanes it carried. 0 where the window holds no such gap (a
rehearsal; never 45 s on the chip)."""
LAYER = 'engine (serving/engine.py)'
UNIT = 'ms'
BETTER = 'lower'
SOURCE = 'program_span'


from harness import gaps


def read(run):
    return gaps.median(run, 'plain')
