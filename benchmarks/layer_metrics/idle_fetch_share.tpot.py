"""Share of the traced window in which the device was idle and the host was inside a
`*.fetch` span (`paged.decode.fetch`, `paged.prefill.fetch`, `exe.fetch`)."""
LAYER = 'device'
UNIT = '%'
BETTER = 'lower'
SOURCE = 'device_trace'


from harness import spans


def read(run):
    v = spans.of_run(run)['idle']
    return v['fetch'] if v else None
