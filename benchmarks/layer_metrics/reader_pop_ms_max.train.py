"""The `host_op:read` span (the py_reader's pop inside `exe.run`), the largest of the
window's steps: a starved input pipeline shows here."""
LAYER = 'input (reader/pipeline.py)'
UNIT = 'ms'
BETTER = 'lower'
SOURCE = 'program_span'


from harness import spans


def read(run):
    v = spans.of_run(run)['training']
    return max(v['pop_ms']) if v and v['pop_ms'] else None
