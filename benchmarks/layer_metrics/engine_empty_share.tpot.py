"""The `serve.idle` spans (a worker with no lane and an empty queue) clipped to the judged
window, over its length: how much of the window the engine was empty. From the span
buffer, no device trace (`harness/idle_account.py`); 0 where it never was."""
LAYER = 'engine (serving/engine.py)'
UNIT = '%'
BETTER = 'lower'
SOURCE = 'program_span'


from harness import idle_account


def read(run):
    return idle_account.host_value(run, 'engine_empty_share')
