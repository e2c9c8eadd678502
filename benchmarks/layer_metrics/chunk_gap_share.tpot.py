"""`chunk` gaps (a step in flight, at least one prefill chunk in front) as % of all gaps
of the judged requests over the whole window."""
LAYER = 'engine (serving/engine.py)'
UNIT = '%'
BETTER = 'lower'
SOURCE = 'program_span'


from harness import gaps


def read(run):
    return gaps.share(run, 'chunk')
