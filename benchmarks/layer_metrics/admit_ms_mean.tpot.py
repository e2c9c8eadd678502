"""Length of the `serve.admit` spans of the judged window that admitted (`admitted` >= 1),
over the streams they opened or resumed: a request's admission on the host (prefix match,
table rows). From the span buffer (`harness/idle_account.py`); 0 where none arrived."""
LAYER = 'engine (serving/engine.py)'
UNIT = 'ms'
BETTER = 'lower'
SOURCE = 'program_span'


from harness import idle_account


def read(run):
    return idle_account.host_value(run, 'admit_ms_mean')
