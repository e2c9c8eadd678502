"""Time from a judged request's slot to its first token: its `serve.prefill` span
(admitted_at -> first_token_at: its chunks, and the decode steps between them),
90th percentile of the requests due inside the window."""
LAYER = 'engine (serving/engine.py)'
UNIT = 'ms'
BETTER = 'lower'
SOURCE = 'program_span'


from harness import spans


def read(run):
    v = spans.of_run(run)['serving']
    return spans.percentile(v['prefill_ms'], 0.90) \
        if v and v['prefill_ms'] else None
