"""Mean length of `paged.prefix.match` in the judged window: the one question a stream's
opening asks the prefix cache (`match` / `match_window` / `match_state`). From the span
buffer (`harness/idle_account.py`); 0 where no stream opened."""
LAYER = 'cache (serving/paging.py)'
UNIT = 'ms'
BETTER = 'lower'
SOURCE = 'program_span'


from harness import idle_account


def read(run):
    return idle_account.host_value(run, 'prefix_match_ms_mean')
