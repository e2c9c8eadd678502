"""Host time of a decode call before the device has its program: `paged.decode.tables`
(page tables, copy-on-write, feed arrays) + the `exe.run` that follows (feed placement,
prepared-program lookup, argument gather, dispatch), mean over the decode steps
while the judged requests ran."""
LAYER = 'model step (serving/paged.py programs)'
UNIT = 'ms'
BETTER = 'lower'
SOURCE = 'program_span'


from harness import spans


def read(run):
    v = spans.of_run(run)['serving']
    return spans.mean([c['prep'] for c in v['decode_calls']]) if v else None
