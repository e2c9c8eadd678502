"""With no snapshot rows, which is what every call and configuration
file before that size existed gives, the serving programs of the four
block families trace to what they traced to on the commit before it (PR
44, 1b3d81a; tests/serving_jaxprs_pr44.json, recorded there by
tests/serving_jaxprs.py), and so do the pieces the new block shares with
them. A digest that moves means another executable for a cell of the
benchmark: another cache key, another set-up, other numbers.

The fifth family, with and without snapshot rows (prefill, page copy,
decode, snapshot, adopt), and the GPT family under speculation (the
verify program and the self-draft's pair beside the target's) are held
to the record of PR 47 (430eb2b; tests/serving_jaxprs_pr47.json), made
before the per-family paged builders became one."""
import json
import os

import pytest

import serving_jaxprs


def _recorded(name):
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           name)) as f:
        return json.load(f)


RECORDED = _recorded('serving_jaxprs_pr44.json')
RECORDED_PR47 = _recorded('serving_jaxprs_pr47.json')
RECORDED_PR49 = _recorded('serving_jaxprs_pr49.json')
RECORDED_PR52 = _recorded('serving_jaxprs_pr52.json')
RECORDED_PR57 = _recorded('serving_jaxprs_pr57.json')
RECORDED_PR60 = _recorded('serving_jaxprs_pr60.json')


@pytest.mark.parametrize('name', ['gpt2', 'hybrid', 'nemotron_h', 'axk1'])
def test_served_with_no_snapshot_rows_a_model_traces_as_before(name):
    assert serving_jaxprs.served(name) == RECORDED[name]


def test_the_shared_pieces_trace_as_before():
    got = serving_jaxprs.pieces()
    new = {'paged_block_attention_4_rows',      # PR 57's, held below
           'paged_attention_d64'}               # PR 60's, held below
    assert {k: v for k, v in got.items() if k not in new} \
        == {k: RECORDED[k] for k in got if k not in new}
    assert got['paged_block_attention_4_rows'] == \
        RECORDED_PR57['paged_block_attention_4_rows']


def test_the_fifth_family_traces_as_before():
    assert serving_jaxprs.served('granite_h') == RECORDED_PR47['granite_h']


def test_the_sixth_family_traces_as_recorded():
    """smallthinker (a second page table, rotary K/V-head pools): the
    record of the PR that added it (PR 49)."""
    assert serving_jaxprs.served('smallthinker') == \
        RECORDED_PR49['smallthinker']


def test_the_seventh_family_traces_as_recorded():
    """solar_open2 (the rule with a decay a key channel, gated
    attention, an expert sublayer a layer): the record of the PR that
    added it (PR 52)."""
    assert serving_jaxprs.served('solar_open2') == \
        RECORDED_PR52['solar_open2']


def test_the_eighth_family_traces_as_recorded():
    """sdar_moe (generation by diffusion over blocks: a prefill chunk
    masked by block, the page copy, the block step with its unmasking
    behind the head): the record of the PR that added it (PR 57)."""
    assert serving_jaxprs.served('sdar_moe') == RECORDED_PR57['sdar_moe']


def test_the_older_families_trace_as_the_parent_of_the_eighth():
    """PR 57's record was written on its finished tree and holds every
    family: what it says of the seven older ones is what their own
    records say (nothing the other cells run changed its text)."""
    older = dict(RECORDED_PR47, **{k: RECORDED_PR49[k] for k in RECORDED_PR49})
    older.update(RECORDED_PR52)
    new = {'sdar_moe', 'paged_block_attention_4_rows'}
    assert {k: v for k, v in RECORDED_PR57.items() if k not in new} \
        == {k: older[k] for k in RECORDED_PR57 if k not in new}


@pytest.mark.parametrize('key', sorted(serving_jaxprs.DEPLOYED))
def test_a_deployment_with_more_programs_traces_as_before(key):
    name, deployment = serving_jaxprs.DEPLOYED[key]
    recorded = RECORDED_PR52 if key in RECORDED_PR52 else RECORDED_PR47
    got = serving_jaxprs.served(name, **deployment)
    assert got == recorded[key]
    # the programs every deployment of the model runs are among them
    assert set(recorded[name]) <= set(got)


def test_the_two_records_agree_where_both_speak():
    assert {k: RECORDED_PR47[k] for k in RECORDED} == RECORDED


def test_the_ninth_family_traces_as_recorded():
    """lfm2 (short convolutions whose rows lie by the page: a prefill
    chunk, the page copy with the conv pools' copies, the decode step;
    no state copy program): the record of the PR that added it (PR 60),
    and the kernel as its decode step calls it, on pairs of heads."""
    assert serving_jaxprs.served('lfm2') == RECORDED_PR60['lfm2']
    assert len(RECORDED_PR60['lfm2']) == 3
    assert serving_jaxprs.pieces()['paged_attention_d64'] == \
        RECORDED_PR60['paged_attention_d64']


def test_the_older_families_trace_as_the_parent_of_the_ninth():
    """PR 60's record was written on its finished tree and holds every
    family: what it says of the eight older ones, their deployments and
    the shared pieces is what PR 57's record says (`short_conv` with
    silu, the kernel at whole lane rows and the plain `match` families'
    programs did not change their text)."""
    new = {'lfm2', 'paged_attention_d64'}
    assert {k: v for k, v in RECORDED_PR60.items() if k not in new} \
        == RECORDED_PR57
