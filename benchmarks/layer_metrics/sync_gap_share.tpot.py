"""`sync` gaps (the step dispatched with nothing in flight: behind a prompt's last chunk,
a burst's first) as % of all gaps of the judged requests over the whole window, a
request's own first gap included."""
LAYER = 'engine (serving/engine.py)'
UNIT = '%'
BETTER = 'lower'
SOURCE = 'program_span'


from harness import gaps


def read(run):
    return gaps.share(run, 'sync')
