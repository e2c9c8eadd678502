"""The host's own section of a pass of the worker loop: the `serve.iter` span's length
less its `wait_ms` (blocked in a step's fetch), mean over the passes of the judged window
that dispatched a decode step and no prefill chunk (`step` 1, `chunk` 0). 0 where the
window holds no such pass (a rehearsal; never 45 s on the chip)."""
LAYER = 'engine (serving/engine.py)'
UNIT = 'ms'
BETTER = 'lower'
SOURCE = 'program_span'


from harness import gaps


def read(run):
    return gaps.host_section(run)
