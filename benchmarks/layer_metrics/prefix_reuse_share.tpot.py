"""Prompt tokens served from registered pages over prompt tokens admitted in the window:
the program's `serving.prefix_tokens_reused` over `serving.prompt_tokens_admitted`
(counted at open_stream; the rest of a prompt is prefilled)."""
LAYER = 'model step (serving/paged.py programs)'
UNIT = '%'
BETTER = 'higher'
SOURCE = 'program_counter'


def read(run):
    c = run['counters']
    if not c.get('prompt_tokens_admitted'):
        return None
    return 100.0 * c['prefix_tokens_reused'] / c['prompt_tokens_admitted']
