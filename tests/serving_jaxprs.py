"""What the serving programs of the block families trace to, as
digests: every program a PagedDecodePredictor runs for a tiny model of
each family (prefill chunk, page copy, decode step; with snapshot rows
also the two state copies; under speculation the verify program and the
self-draft's pair), and the three shared pieces a new block could
disturb (the sigmoid gate of moe_experts, ssd_chunk in blocks of 128,
the Pallas paged_attention at 2, 16 and 32 pool heads). A jaxpr's text
holds no file name and no line number, so a digest moves only when what
is computed moves.

    JAX_PLATFORMS=cpu python tests/serving_jaxprs.py > FILE

writes the record; tests/test_serving_jaxprs.py holds the tree to the
one recorded from the parent of the PR that added snapshot rows
(tests/serving_jaxprs_pr44.json: four families, no snapshot rows) and to
the one recorded from the parent of the PR that left one paged builder
(tests/serving_jaxprs_pr47.json: the same, and granite_h with snapshot
rows and the GPT family under speculation). The sixth family
(smallthinker: two page tables) is held to the record of the PR that
added it (tests/serving_jaxprs_pr49.json), and so is the seventh
(solar_open2: the rule with a decay a key channel, with and without
snapshot rows; tests/serving_jaxprs_pr52.json), and the eighth (sdar_moe:
generation by diffusion over blocks; its pair's second program is a
block step, which `served` drives through block_step; with it the piece
`paged_block_attention_4_rows`, the Pallas kernel as the block step's op
calls it; tests/serving_jaxprs_pr57.json), and the ninth (lfm2: short
convolutions whose rows lie by the page, K/V heads of 64 two a lane row;
with it the piece `paged_attention_d64`, the kernel as the decode step's
op calls it on pairs; tests/serving_jaxprs_pr60.json).
"""
import hashlib
import json
import tempfile

import jax
import numpy as np

import paddle_tpu as fluid
from paddle_tpu import unique_name
from paddle_tpu.framework import Program, program_guard
from paddle_tpu.inference import AnalysisConfig, AnalysisPredictor

GEOMETRY = dict(slots=3, page_tokens=4, kv_pages=13, prefill_chunk=8)
T = 16


def _digest(text):
    return hashlib.sha256(text.encode()).hexdigest()[:16]


def _models():
    from paddle_tpu.models import (axk1, granite_h, hybrid, lfm2,
                                   nemotron_h, sdar_moe, smallthinker,
                                   solar_open2, transformer)
    return {
        'gpt2': (transformer.language_model_logits,
                 transformer.TransformerConfig(
                     vocab=64, dim=32, heads=2, layers=2, ffn=64, max_len=T,
                     use_tp=False, use_sp=False)),
        'hybrid': (hybrid.language_model_logits, hybrid.HybridConfig(
            vocab=64, dim=32, heads=2, ffn=64, max_len=T, key_dim=8,
            value_dim=16)),
        'nemotron_h': (nemotron_h.language_model_logits,
                       nemotron_h.NemotronHConfig(
                           vocab=64, dim=32, max_len=T, head_dim=8,
                           expert_offset=4, experts_held=8)),
        'axk1': (axk1.language_model_logits, axk1.AXK1Config(max_len=T)),
        'granite_h': (granite_h.language_model_logits,
                      granite_h.GraniteHConfig(
                          vocab=64, dim=32, max_len=T, head_dim=8,
                          layer_types=('mamba', 'attention', 'mamba'),
                          expert_offset=4, experts_held=8)),
        'smallthinker': (smallthinker.language_model_logits,
                         smallthinker.SmallThinkerConfig(
                             vocab=64, dim=32, max_len=T, head_dim=8,
                             window=6)),
        'solar_open2': (solar_open2.language_model_logits,
                        solar_open2.SolarOpen2Config(
                            vocab=64, dim=32, max_len=T, head_dim=8,
                            key_dim=8, value_dim=8, gate_rank=4,
                            expert_offset=4, experts_held=8)),
        'sdar_moe': (sdar_moe.language_model_logits,
                     sdar_moe.SdarMoeConfig(
                         vocab=64, dim=32, max_len=T, head_dim=8,
                         expert_offset=4, experts_held=8)),
        'lfm2': (lfm2.language_model_logits, lfm2.Lfm2Config(
            vocab=64, dim=32, max_len=T, head_dim=64,
            layer_types=('conv', 'full_attention', 'conv'))),
    }


def _predictor(logits_fn, cfg, tmp):
    main, startup = Program(), Program()
    with program_guard(main, startup), unique_name.guard():
        tokens = fluid.layers.data('tokens', shape=[1, cfg.max_len, 1],
                                   dtype='int64', append_batch_size=False)
        logits = logits_fn(tokens, cfg)
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        fluid.io.save_inference_model(tmp, ['tokens'], [logits], exe,
                                      main_program=main)
    return AnalysisPredictor(AnalysisConfig(tmp, place=fluid.CPUPlace()))


def program_digests(dec):
    """Sorted digests of the jaxprs of every device segment the
    decoder's executor (and its draft's, under speculation) has
    compiled."""
    out = []
    for exe in [dec._exe] + ([dec.draft._exe] if hasattr(dec, 'draft')
                             else []):
        for prepared in exe._prepared_cache.values():
            for step in prepared.steps:
                if getattr(step, 'jitted', None) is not None \
                        and getattr(step, '_arg_struct', None) is not None:
                    out.append(_digest(str(
                        step.jitted.trace(*step._arg_struct).jaxpr)))
    return sorted(out)


def served(name, **deployment):
    """The digests of model `name` served with no snapshot rows, or as
    `deployment` (prepare_decoding's arguments beside GEOMETRY) says:
    snapshot_rows adds the two state copy programs, speculative the
    verify program and the self-draft's prefill, decode and page copy."""
    logits_fn, cfg = _models()[name]
    with tempfile.TemporaryDirectory() as tmp:
        dec = _predictor(logits_fn, cfg, tmp).prepare_decoding(
            **dict(GEOMETRY, **deployment))
    if dec.block_tokens:
        # whole blocks of the prompt, then one pass over the first block
        dec.open_stream(1, np.arange(1, 12))
        start = None
        while start is None:
            start = dec.prefill_step(1)
        blk = dec.new_block(start.start, start.tail)
        ids = np.zeros((dec.slots, dec.block_tokens), np.int64)
        starts = np.zeros(dec.slots, np.int32)
        ids[1], starts[1] = blk.ids, blk.start
        dec.block_step(ids, starts, starts * 0 + 1, [1])
        return program_digests(dec)
    dec.prefill([np.arange(1, 12)], [1])
    tokens = np.zeros(dec.slots, np.int64)
    positions = np.zeros(dec.slots, np.int32)
    tokens[1], positions[1] = 5, 11
    if deployment.get('speculative'):
        dec.spec_step(tokens, positions)
    dec.decode_step(tokens, positions)
    return program_digests(dec)


# beside every family with no snapshot rows: what PR 44's record lacks
DEPLOYED = {
    'granite_h_snapshot_rows': ('granite_h', dict(snapshot_rows=2)),
    'gpt2_speculative': ('gpt2', dict(speculative=True, spec_k=2,
                                      draft_layers=1)),
    'solar_open2_snapshot_rows': ('solar_open2', dict(snapshot_rows=2)),
}


def pieces():
    from paddle_tpu.ops import moe_ops, ssd_ops
    from paddle_tpu.pallas import paged_attention as pa
    f4 = np.float32
    out = {'moe_sigmoid_gate': _digest(str(jax.make_jaxpr(
        lambda *a: moe_ops.served_weights(*a, 22, 5.0))(
            np.zeros((5, 24), f4), np.zeros((24, 32), f4),
            np.zeros(32, f4))))}
    h, p, g, n, t = 8, 16, 2, 128, 256
    out['ssd_chunk_128'] = _digest(str(jax.make_jaxpr(
        lambda *a: ssd_ops.ssd_chunk(*a, block=128))(
            np.zeros((h, p, n), f4), np.zeros((t, h, p), f4),
            np.zeros((t, g, n), f4), np.zeros((t, g, n), f4),
            np.zeros((t, h), f4), np.zeros((t, h), f4), np.zeros(h, f4))))
    for kvh in (2, 16, 32):
        out['paged_attention_%d_pool_heads' % kvh] = _digest(str(
            jax.make_jaxpr(lambda *a: pa.paged_attention(
                *a, sm_scale=0.0883883461356163))(
                    np.zeros((4, 32, 128), f4),
                    np.zeros((40, 16, kvh, 128), f4),
                    np.zeros((40, 16, kvh, 128), f4),
                    np.zeros((4, 8), np.int32), np.zeros(4, np.int32))))
    out['paged_block_attention_4_rows'] = _digest(str(jax.make_jaxpr(
        lambda *a: pa.paged_attention(
            *a, sm_scale=0.0883883461356163, name='paged_block_attention'))(
                np.zeros((4, 128, 128), f4), np.zeros((40, 16, 4, 128), f4),
                np.zeros((40, 16, 4, 128), f4), np.zeros((4, 8), np.int32),
                np.zeros(4, np.int32))))
    out['paged_attention_d64'] = _digest(str(jax.make_jaxpr(
        lambda *a: pa.paged_attention_d64(*a, sm_scale=0.125))(
            np.zeros((4, 32, 64), f4), np.zeros((40, 16, 4, 128), f4),
            np.zeros((40, 16, 4, 128), f4), np.zeros((4, 8), np.int32),
            np.zeros(4, np.int32))))
    return out


def record():
    out = {name: served(name) for name in _models()}
    out.update({key: served(name, **deployment)
                for key, (name, deployment) in DEPLOYED.items()})
    return dict(out, **pieces())


if __name__ == '__main__':
    print(json.dumps(record(), indent=1))
