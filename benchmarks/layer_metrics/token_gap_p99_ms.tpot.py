"""The 99th percentile of ALL gaps between consecutive tokens of the judged requests
(`gaps_ms` of their `serve.decode` spans, from `Request.token_at`; about 7000 a
window): a prefill chunk between two decode steps shows here, not in the median."""
LAYER = 'engine (serving/engine.py)'
UNIT = 'ms'
BETTER = 'lower'
SOURCE = 'program_span'


from harness import spans


def read(run):
    v = spans.of_run(run)['serving']
    return spans.percentile(v['gaps_ms'], 0.99) if v and v['gaps_ms'] else None
