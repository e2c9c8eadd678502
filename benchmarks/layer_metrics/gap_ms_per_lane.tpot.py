"""Least-squares slope of the `plain` gaps of the judged requests on the lanes their
steps carried (`gap_lanes`): what one more live lane costs every token of the step. 0
where there is no plain gap or all carried the same lanes (a rehearsal; never 45 s on the
chip)."""
LAYER = 'engine (serving/engine.py)'
UNIT = 'ms'
BETTER = 'lower'
SOURCE = 'program_span'


from harness import gaps


def read(run):
    return gaps.slope(run)
