"""Time a judged request waited for a slot: its `serve.queue` span (submitted_at ->
admitted_at, the engine's own timestamps), 90th percentile of the requests due
inside the window. With `prefill_wall_p90_ms.tpot` it splits `ttft_p90_ms.tpot`."""
LAYER = 'engine (serving/engine.py)'
UNIT = 'ms'
BETTER = 'lower'
SOURCE = 'program_span'


from harness import spans


def read(run):
    v = spans.of_run(run)['serving']
    return spans.percentile(v['queue_ms'], 0.90) if v and v['queue_ms'] else None
