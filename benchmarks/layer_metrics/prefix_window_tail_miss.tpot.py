"""Streams opened in the window for which the prefix cache held a deeper boundary in the full
pool than it could hand out, because that boundary's window pages were not resident: the
program's `serving.prefix.window_tail_miss` (PrefixCache.match_window). 0 while the window
pool holds the corpus's tails: under the knee."""
LAYER = 'cache (serving/paging.py)'
UNIT = 'count'
BETTER = 'lower'
SOURCE = 'program_counter'


def read(run):
    return run['counters'].get('prefix_window_tail_miss')
