"""The five serving programs, op for op.

What each builder of models/transformer.py and models/hybrid.py emits
for a tiny spec (its op-type sequence, its feed names, its fetch names)
against a list recorded from the commit before the dense ring cache was
deleted (PR 27, d61f26e; tests/serving_programs_pr27.json). The paged
programs are what every benchmark cell compiles: a change that disturbs
a builder changes the executables, their cache keys and the numbers, and
has to record a new list here on purpose.

PR 44 did, for the decode programs alone: they hold no kv_page_cow and
take no decode_cow_* feed (tests/serving_programs_pr44.json, which also
records the page copy program that took the copies' place). The prefill
and verify programs are still held to the PR 27 file: their executables
were not to change.

PR 48 put the eight per-family builders and these two under one prefill
and one decode builder. Before it did, the lists of the other three
families (nemotron_h, axk1, granite_h: prefill and decode) were recorded
from its parent (PR 47, 430eb2b; tests/serving_programs_pr47.json):

    JAX_PLATFORMS=cpu PYTHONPATH=. python tests/test_serving_programs.py

prints that record.
"""
import json
import os

import pytest

import paddle_tpu as fluid
from paddle_tpu import unique_name
from paddle_tpu.framework import Program, program_guard
from paddle_tpu.models import axk1, granite_h, hybrid, nemotron_h
from paddle_tpu.models.transformer import (TransformerConfig,
                                           language_model_logits)
from paddle_tpu.transpiler.decode_transpiler import DecodeTranspiler

def _recorded(name):
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           name)) as f:
        return json.load(f)


RECORDED = _recorded('serving_programs_pr27.json')
RECORDED.update(_recorded('serving_programs_pr44.json'))
RECORDED.update(_recorded('serving_programs_pr47.json'))

GEOMETRY = dict(slots=3, page_tokens=4, kv_pages=13, prefill_chunk=8)


def _lm_program(logits_fn, cfg):
    main, startup = Program(), Program()
    with program_guard(main, startup), unique_name.guard():
        tokens = fluid.layers.data('tokens', shape=[1, cfg.max_len, 1],
                                   dtype='int64', append_batch_size=False)
        logits_fn(tokens, cfg)
    return main


def _describe(program, feeds, fetches):
    return {'ops': ' '.join(op.type for op in program.global_block().ops),
            'feeds': list(feeds), 'fetches': [v.name for v in fetches]}


# the families PR 27 and PR 44 did not record
LATER = {
    'nemotron_h': (nemotron_h.language_model_logits,
                   nemotron_h.NemotronHConfig(
                       vocab=64, dim=32, max_len=16, head_dim=8,
                       expert_offset=4, experts_held=8)),
    'axk1': (axk1.language_model_logits, axk1.AXK1Config(max_len=16)),
    'granite_h': (granite_h.language_model_logits,
                  granite_h.GraniteHConfig(
                      vocab=64, dim=32, max_len=16, head_dim=8,
                      layer_types=('mamba', 'attention', 'mamba'),
                      expert_offset=4, experts_held=8)),
}


def _later_programs():
    out = {}
    for name, (logits_fn, cfg) in LATER.items():
        lm = _lm_program(logits_fn, cfg)
        with unique_name.guard():
            pair = DecodeTranspiler().transpile(lm, **GEOMETRY)
        out[name + '_prefill'] = _describe(
            pair.prefill_program, pair.prefill_feeds, pair.prefill_fetches)
        out[name + '_decode'] = _describe(
            pair.decode_program, pair.decode_feeds, pair.decode_fetches)
    return out


@pytest.fixture(scope='module')
def programs():
    return dict(_first_programs(), **_later_programs())


def _first_programs():
    gpt2 = _lm_program(language_model_logits, TransformerConfig(
        vocab=64, dim=32, heads=2, layers=2, ffn=64, max_len=16,
        use_tp=False, use_sp=False))
    with unique_name.guard():
        spair = DecodeTranspiler().transpile_spec(
            gpt2, spec_k=2, draft_layers=1, **GEOMETRY)
    hyb = _lm_program(hybrid.language_model_logits, hybrid.HybridConfig(
        vocab=64, dim=32, heads=2, ffn=64, max_len=16, key_dim=8,
        value_dim=16))
    with unique_name.guard():
        hpair = DecodeTranspiler().transpile(hyb, **GEOMETRY)
    t = spair.target
    return {
        'gpt2_prefill': _describe(t.prefill_program, t.prefill_feeds,
                                  t.prefill_fetches),
        'gpt2_decode': _describe(t.decode_program, t.decode_feeds,
                                 t.decode_fetches),
        'gpt2_verify': _describe(spair.verify_program, spair.verify_feeds,
                                 spair.verify_fetches),
        'hybrid_prefill': _describe(hpair.prefill_program,
                                    hpair.prefill_feeds,
                                    hpair.prefill_fetches),
        'hybrid_decode': _describe(hpair.decode_program, hpair.decode_feeds,
                                   hpair.decode_fetches),
        'gpt2_page_copy': _describe(t.copy_program, t.copy_feeds, ()),
        'hybrid_page_copy': _describe(hpair.copy_program, hpair.copy_feeds,
                                      ())}


@pytest.mark.parametrize('name', sorted(RECORDED))
def test_serving_program_is_op_for_op_what_it_was(programs, name):
    got, want = programs[name], RECORDED[name]
    assert got['feeds'] == want['feeds']
    assert got['fetches'] == want['fetches']
    assert got['ops'].split() == want['ops'].split()


def test_only_the_decode_lists_were_recorded_again():
    """The prefill and verify programs are held to what PR 27 recorded;
    the decode programs differ from it by the copies alone."""
    old = _recorded('serving_programs_pr27.json')
    new = _recorded('serving_programs_pr44.json')
    assert sorted(new) == ['gpt2_decode', 'gpt2_page_copy',
                           'hybrid_decode', 'hybrid_page_copy']
    for name in ('gpt2_decode', 'hybrid_decode'):
        assert [op for op in old[name]['ops'].split()
                if op != 'kv_page_cow'] == new[name]['ops'].split()
        assert [f for f in old[name]['feeds'] if 'cow' not in f] \
            == new[name]['feeds']
        assert old[name]['fetches'] == new[name]['fetches']
    for name in ('gpt2_page_copy', 'hybrid_page_copy'):
        assert set(new[name]['ops'].split()) == {'kv_page_cow'}


if __name__ == '__main__':
    print(json.dumps(_later_programs(), indent=1, sort_keys=True))
