"""Summed length of `paged.prefix.evict` in the judged window, over its seconds: host time
the prefix cache's eviction scans take, in ms a second. From the span buffer
(`harness/idle_account.py`); 0 where no pool ran dry."""
LAYER = 'cache (serving/paging.py)'
UNIT = 'ms/s'
BETTER = 'lower'
SOURCE = 'program_span'


from harness import idle_account


def read(run):
    return idle_account.host_value(run, 'evict_ms_per_s')
