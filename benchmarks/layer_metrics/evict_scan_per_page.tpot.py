"""Summed `scanned` over summed `freed` of the judged window's `paged.prefix.evict` spans:
cache entries looked at for each page given back, what an amortised LRU brings to about 1.
From the span buffer (`harness/idle_account.py`); 0 where no pool ran dry."""
LAYER = 'cache (serving/paging.py)'
UNIT = 'entries'
BETTER = 'lower'
SOURCE = 'program_span'


from harness import idle_account


def read(run):
    return idle_account.host_value(run, 'evict_scan_per_page')
