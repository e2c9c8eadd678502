"""The most pages the KV pool had handed out after any prefill or decode step of the
window (`pool_stats()['pages_in_use']`, read by the wrapper on the decoder instance):
open streams and what the prefix cache keeps of finished prompts until it must evict."""
LAYER = 'model step (serving/paged.py programs)'
UNIT = 'count'
BETTER = 'lower'
SOURCE = 'program_counter'


def read(run):
    return run['counters'].get('kv_pages_in_use_max')
