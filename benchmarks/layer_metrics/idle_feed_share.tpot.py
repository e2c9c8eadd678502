"""Share of the traced window in which the device was idle and the host was preparing
or dispatching the next program: in a `*.tables` span, `exe.feed`, `exe.prepare`, the
rest of `exe.run`, or a `device_segment:*` dispatch (`pt.` spans in the capture)."""
LAYER = 'device'
UNIT = '%'
BETTER = 'lower'
SOURCE = 'device_trace'


from harness import spans


def read(run):
    v = spans.of_run(run)['idle']
    return v['feed'] if v else None
