"""Reused prompt tokens that came from a boundary of whole pages at which no prompt had ever
ended (a system prompt that only came in as the head of longer prompts), over reused prompt
tokens: the program's `serving.prefix.offprompt_tokens` over `serving.prefix_tokens_reused`.
What a design that keeps recurrent state at prompts' ends alone could not hand out."""
LAYER = 'cache (serving/paging.py)'
UNIT = '%'
BETTER = 'higher'
SOURCE = 'program_counter'


def read(run):
    c = run['counters']
    if not c.get('prefix_tokens_reused') or 'prefix_offprompt_tokens' not in c:
        return None
    return 100.0 * c['prefix_offprompt_tokens'] / c['prefix_tokens_reused']
