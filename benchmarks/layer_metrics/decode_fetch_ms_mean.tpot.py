"""Host time of a decode call in `paged.decode.fetch` (`np.asarray(ids)`: the wait for
the device and the transfer), mean over the decode steps while the judged requests ran."""
LAYER = 'model step (serving/paged.py programs)'
UNIT = 'ms'
BETTER = 'lower'
SOURCE = 'program_span'


from harness import spans


def read(run):
    v = spans.of_run(run)['serving']
    return spans.mean([c['fetch'] for c in v['decode_calls']]) if v else None
