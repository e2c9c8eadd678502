"""A served model is described once, by its block file. The description
language_model_logits leaves on the program ({'family', 'config'}:
Program.served_model) survives clone, _prune and save_inference_model,
and is what the DecodeTranspiler makes the spec from: the spec its
family's spec_from_config gives, held to the loaded program's variables.
A program without one is read as the GPT shape or refused, and the
refusal says how a program is recognised."""
import json

import pytest

import paddle_tpu as fluid
from paddle_tpu import models, unique_name
from paddle_tpu.framework import Program, program_guard
from paddle_tpu.transpiler.decode_transpiler import (
    DecodeTranspileError, extract_decode_spec)

import serving_jaxprs

FAMILIES = list(models.SERVED_FAMILIES)


def _lm(name):
    logits_fn, cfg = serving_jaxprs._models()[name]
    main, startup = Program(), Program()
    with program_guard(main, startup), unique_name.guard():
        tokens = fluid.layers.data('tokens', shape=[1, cfg.max_len, 1],
                                   dtype='int64', append_batch_size=False)
        logits = logits_fn(tokens, cfg)
    return main, logits, cfg


@pytest.fixture(scope='module')
def loaded(tmp_path_factory):
    """{family: (the predictor over its saved model, its Config)}."""
    out = {}
    for name in FAMILIES:
        logits_fn, cfg = serving_jaxprs._models()[name]
        out[name] = (serving_jaxprs._predictor(
            logits_fn, cfg, str(tmp_path_factory.mktemp(name))), cfg)
    return out


def test_the_tuple_names_every_block_file_written_for_serving():
    assert sorted(FAMILIES) == ['axk1', 'granite_h', 'hybrid', 'lfm2',
                                'nemotron_h', 'sdar_moe', 'smallthinker',
                                'solar_open2']
    for name in FAMILIES:
        family = models.served_family(name)
        assert family.__name__ == 'paddle_tpu.models.' + name
        assert callable(family.spec_from_config) and family.Config
    assert models.served_family('transformer') is None


@pytest.mark.parametrize('name', FAMILIES)
def test_the_description_survives_saving_and_loading(loaded, name):
    pred, cfg = loaded[name]
    told = pred._program.served_model
    assert told['family'] == name
    family = models.served_family(name)
    # what JSON made lists of comes back as the constructor takes it
    assert vars(family.Config(**told['config'])) == vars(cfg)
    spec = extract_decode_spec(pred._program)
    want = family.spec_from_config(cfg)
    assert type(spec) is type(want)
    if hasattr(spec, 'cfg'):
        assert vars(spec.cfg) == vars(cfg)
    assert spec.blocks == want.blocks and spec.kinds == want.kinds
    assert spec.param_names() == want.param_names()
    assert spec.param_specs == {n: None for n in want.param_names()}
    assert spec.pool_shape(5, 4) == want.pool_shape(5, 4)


@pytest.mark.parametrize('name', FAMILIES)
def test_clone_and_prune_keep_the_description(name):
    main, logits, cfg = _lm(name)
    want = {'family': name, 'config': dict(vars(cfg))}
    assert main.served_model == want
    test = main.clone(for_test=True)
    assert test.served_model == want
    assert test._prune([logits], ['tokens']).served_model == want
    assert Program.from_json(test.to_json()).served_model == \
        json.loads(json.dumps(want))
    # a copy's description is its own
    test.served_model['family'] = 'other'
    assert main.served_model == want


@pytest.mark.parametrize('name', FAMILIES)
def test_a_described_weight_the_program_lacks_is_refused_by_name(
        loaded, name):
    prog = loaded[name][0]._program.clone()
    gone = extract_decode_spec(prog).param_names()[-1]
    del prog.global_block().vars[gone]
    with pytest.raises(DecodeTranspileError, match=gone.replace('.', r'\.')):
        extract_decode_spec(prog)
    # and one that is there but is no parameter any more
    prog = loaded[name][0]._program.clone()
    prog.global_block().vars[gone].persistable = False
    with pytest.raises(DecodeTranspileError, match='not a persistable'):
        extract_decode_spec(prog)


@pytest.mark.parametrize('name', FAMILIES)
def test_a_described_weight_of_another_shape_is_refused_by_name(
        loaded, name):
    pred, cfg = loaded[name]
    spec = extract_decode_spec(pred._program)
    names = [spec.emb_w] + [spec.blocks[i]['qkv'][0] for i in spec.kv_layers
                            if spec.page_kind == 'kv']
    for victim in names:
        prog = pred._program.clone()
        var = prog.global_block().vars[victim]
        var.shape = tuple(var.shape[:-1]) + (var.shape[-1] + 8,)
        with pytest.raises(DecodeTranspileError,
                           match=victim.replace('.', r'\.') + '.* is '):
            extract_decode_spec(prog)
    # a description of other widths than the weights saved with it
    prog = pred._program.clone()
    prog.served_model['config']['dim'] = cfg.dim * 2
    with pytest.raises(DecodeTranspileError, match=r'embed\.w'):
        extract_decode_spec(prog)


@pytest.mark.parametrize('told, match', [
    ({'family': 'llama', 'config': {}}, "family 'llama'"),
    ({'config': {}}, 'family None'),
    ({'family': 'hybrid', 'config': {'width': 3}}, 'HybridConfig'),
    ({'family': 'hybrid'}, 'HybridConfig'),
    ({'family': 'hybrid', 'config': {'layer_types': ['conv']}},
     "layer kind 'conv'")])
def test_a_description_that_names_no_served_model_is_refused(told, match):
    prog, _, _ = _lm('hybrid')
    prog.served_model = told
    with pytest.raises(DecodeTranspileError, match=match) as err:
        extract_decode_spec(prog)
    # the refusal says how a program is recognised: the two ways
    assert 'SERVED_FAMILIES' in str(err.value)
    assert 'models.transformer.language_model' in str(err.value)


def test_a_program_without_a_description_takes_the_gpt_walk():
    prog, _, _ = _lm('gpt2')
    assert prog.served_model is None
    spec = extract_decode_spec(prog)
    assert type(spec).__name__ == 'DecodeSpec' and spec.pos_w
    # a block file's program that lost its description is not guessed at
    prog, _, _ = _lm('axk1')
    prog.served_model = None
    with pytest.raises(DecodeTranspileError, match='no position_embedding'):
        extract_decode_spec(prog)
