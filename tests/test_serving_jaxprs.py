"""With no snapshot rows, which is what every call and configuration
file before that size existed gives, the serving programs of the four
block families trace to what they traced to on the commit before it (PR
44, 1b3d81a; tests/serving_jaxprs_pr44.json, recorded there by
tests/serving_jaxprs.py), and so do the pieces the new block shares with
them. A digest that moves means another executable for a cell of the
benchmark: another cache key, another set-up, other numbers."""
import json
import os

import pytest

import serving_jaxprs

with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       'serving_jaxprs_pr44.json')) as f:
    RECORDED = json.load(f)


@pytest.mark.parametrize('name', ['gpt2', 'hybrid', 'nemotron_h', 'axk1'])
def test_served_with_no_snapshot_rows_a_model_traces_as_before(name):
    assert serving_jaxprs.served(name) == RECORDED[name]


def test_the_shared_pieces_trace_as_before():
    got = serving_jaxprs.pieces()
    assert got == {k: RECORDED[k] for k in got}
