"""The SDAR block with routed experts (models/sdar_moe.py) at tiny
widths with seeded weights: its whole-sequence program and its paged
pair, whose second program is a BLOCK step (4 rows a lane at the same
positions pass after pass, only a commit's rows count), against the
plain reference (benchmarks/reference/sdar_moe.py): the prefill rows and
every pass's rows for prompts of all four lengths modulo 4, several
chunks and a forked shared page; the eight shares of the experts against
the uncut layer; the block attention op against its gather-and-mask
lowering; the unmasking op against the reference's rule; the prefix
cache's boundaries; the refusals. Pages of 4 tokens and chunks of 8, so
that every edge is crossed. What the ENGINE makes of it is
tests/test_block_step.py."""
import os
import sys

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.inference import AnalysisConfig, AnalysisPredictor
from paddle_tpu.models import sdar_moe
from paddle_tpu.models.transformer import DecodeTranspileError
from paddle_tpu.serving.paging import PagePool, PageTable, PrefixCache

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), 'benchmarks'))
from reference import sdar_moe as ref              # noqa: E402
from builders import sdar_moe as builder           # noqa: E402

MODEL = {'vocab_size': 64, 'hidden_size': 32, 'num_attention_heads': 4,
         'num_key_value_heads': 2, 'head_dim': 8, 'num_experts': 8,
         'num_experts_per_tok': 3, 'moe_intermediate_size': 24,
         'norm_topk_prob': True, 'rms_norm_eps': 1e-6, 'rope_theta': 1e6,
         'rope_scaling': None, 'tie_word_embeddings': False,
         'num_hidden_layers': 3, 'n_positions': 64,
         # wide enough weights that these narrow layers, the routed
         # experts among them, each move the logits by tens of percent
         'initializer_range': 0.3}
SEED = 5700000013
# float32 both sides on the CPU; the program's batched expert products,
# its gathered pages and its fused orders differ from the reference's
# loops by rounding only. The bf16-stored control reads hundreds of
# times this.
TOL = 2e-5


def model_of(**generation):
    return dict(MODEL, generation=dict(
        {'block_length': 4, 'denoising_steps': 4,
         'remasking': 'low_confidence_static'}, **generation))


def build(tmp, model, run_whole=False):
    """(predictor over the saved model, tokens [T], the whole-sequence
    program's logits or None)."""
    dims = ref.dims_of(model)
    cfg = builder.model_config(dims)
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), fluid.unique_name.guard():
        tokens = fluid.layers.data('tokens', shape=[1, cfg.max_len, 1],
                                   dtype='int64', append_batch_size=False)
        logits = sdar_moe.language_model_logits(tokens, cfg)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        builder.put_seeded_weights(
            scope, sdar_moe.spec_from_config(cfg), dims, SEED)
        toks = np.random.default_rng(0).integers(
            1, dims.vocab - 1, size=(1, cfg.max_len, 1))
        full = exe.run(main, feed={'tokens': toks},
                       fetch_list=[logits])[0][0] if run_whole else None
        fluid.io.save_inference_model(str(tmp), ['tokens'], [logits], exe,
                                      main_program=main)
    pred = AnalysisPredictor(AnalysisConfig(str(tmp),
                                            place=fluid.CPUPlace()))
    return pred, toks[0, :, 0], full


DIMS = ref.dims_of(model_of())


@pytest.fixture(scope='module')
def model(tmp_path_factory):
    return build(tmp_path_factory.mktemp('sdar_lm'), model_of(), True)


def decoder(pred, **kw):
    return pred.prepare_decoding(**dict(dict(
        slots=4, paged=True, page_tokens=4, kv_pages=80, prefill_chunk=8),
        **kw))


def test_whole_sequence_program_is_the_reference(model):
    _, toks, full = model
    want = np.asarray(ref.logits(ref.seed_key(SEED), DIMS, toks))
    assert ref.rel_l2(full, want) < TOL
    # and no row sees a later block: a changed last token moves the last
    # block's rows alone
    other = toks.copy()
    other[-1] = (other[-1] + 1) % 60 + 1
    moved = np.asarray(ref.logits(ref.seed_key(SEED), DIMS, other))
    assert np.array_equal(moved[:-4], want[:-4])
    assert not np.allclose(moved[-4:-1], want[-4:-1])


def test_prefill_rows_and_every_pass_are_the_reference(model):
    """Prompts of 8, 21, 30 and 19 tokens (0, 1, 2 and 3 modulo 4; three
    chunks and a padded one among them) beside a parent whose whole
    blocks end half way into a page and a follow-up that opens on its
    registered pages and forks the partly filled one IN A BLOCK STEP
    (nothing of it is left to prefill); chunks of later lanes between
    passes of earlier ones; three blocks each, so that later blocks read
    earlier commits."""
    pred, toks, _ = model
    dec = decoder(pred)
    prompts = [toks[:8], toks[3:24], toks[1:31], toks[5:24],
               toks[10:32], toks[10:33]]            # parent 22 -> whole 20
    slots = [0, 1, 2, 3]
    run = builder.drive_check(dec, [], [], slots, prompts[:4], 3)
    dec.reset()
    more = builder.drive_check(dec, [], [], [0, 2], prompts[4:], 3)
    assert more['shared'] == {0: 0, 2: 20}         # whole blocks, page 5 1/2
    assert more['prefill_row'][2] is None          # nothing left to prefill
    for got, name in ((run, 'four lengths'), (more, 'fork')):
        for slot in got['got']:
            have = np.concatenate([r.reshape(-1, DIMS.vocab)
                                   for r in got['got'][slot]])
            want = builder.passes_reference(
                SEED, DIMS, got['passes'][slot], got['prefill_row'][slot],
                'float32')
            assert have.shape == want.shape, (name, slot)
            rows = np.linalg.norm(have - want, axis=-1) \
                / np.linalg.norm(want, axis=-1)
            assert rows.max() < TOL, (name, slot, rows.max())
    # 3 blocks of 5 passes but the first: 5 - r passes for r fixed tokens
    assert [len(run['passes'][s]) for s in slots] == [15, 14, 13, 12]
    assert dec.jit_cache_stats()['compiled_segments'] == 3


@pytest.mark.parametrize('control, mask', [
    ('causal inside a block', 'causal'),
    ('a prefix adopted inside a block', ('misaligned', 10))])
def test_a_wrong_mask_is_not_the_reference(model, control, mask):
    _, toks, full = model
    wrong = np.asarray(ref.logits(ref.seed_key(SEED), DIMS, toks,
                                  mask=mask))
    assert ref.rel_l2(full, wrong) > 100 * TOL, control


def test_leaving_out_the_commit_is_not_the_reference(model):
    """The control of `correct`: history blocks as their last denoising
    pass was fed them, a row still masked."""
    pred, toks, _ = model
    dec = decoder(pred)
    run = builder.drive_check(dec, [], [], [1], [toks[:9]], 3)
    passes = run['passes'][1]
    stale = builder.uncommitted(passes, 8, DIMS)
    assert stale[:4] == [(list(i), s) for i, s in passes[:4]]
    assert DIMS.mask_id in stale[-1][0][8:12]
    have = np.concatenate([r.reshape(-1, DIMS.vocab)
                           for r in run['got'][1]])
    want = builder.passes_reference(SEED, DIMS, stale, 7, 'float32')
    assert ref.rel_l2(have[:17], want[:17]) < TOL       # the first block
    assert ref.rel_l2(have[17:], want[17:]) > 100 * TOL


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """One expert sublayer through the PROGRAM's op for each of the
    eight shares of 16 experts (router 128 wide, 8 a token), added up,
    is the uncut reference's routed part."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops import moe_ops
    d = ref.dims_of(dict(MODEL, num_experts=128, num_experts_per_tok=8))
    key = ref.seed_key(SEED)
    p = ref.layer_weights(key, 0, d)
    h = jax.random.normal(jax.random.PRNGKey(3), (24, d.dim), jnp.float32)
    whole = np.asarray(ref.routed_part(
        h, p, d, 'float32', lambda e: ref.expert_weights(key, 0, e, d)))
    total = np.zeros_like(whole)
    touched = 0
    for share in range(8):
        held = dict(MODEL, num_experts=16, router_experts=128,
                    num_experts_per_tok=8, expert_offset=16 * share)
        ds = ref.dims_of(held)
        t = ref.layer_tensors(key, 0, ds)
        w = moe_ops.served_weights(h, t['router'], None, 8, 1.0, 1, 1,
                                   'softmax')[:, 16 * share:16 * share + 16]
        total += np.asarray(moe_ops.held_gated_experts(
            h, w, t['w1'], t['w3'], t['w2'], 'silu'))
        touched += int(np.sum(np.asarray(w) != 0))
    assert touched == 24 * 8                    # every pair in one share
    assert ref.rel_l2(total, whole) < TOL


@pytest.mark.parametrize('rows', [1, 4])
def test_block_attention_op_is_its_gather_and_mask_lowering(rows):
    """The kernel (interpret mode) against the reference composition at
    1 and 4 rows a lane, and 1 row equal to paged_attention as it
    stands."""
    import jax.numpy as jnp
    from paddle_tpu.ops import attention_ops as ao
    from paddle_tpu.pallas import paged_attention as pa
    rng = np.random.default_rng(rows)
    S, H, KVH, dh, pt, P, N = 3, 4, 2, 128, 8, 4, 14
    q = jnp.asarray(rng.standard_normal((S, rows, H, dh)), jnp.float32)
    kp, vp = (jnp.asarray(rng.standard_normal((N, pt, KVH, dh)), jnp.float32)
              for _ in range(2))
    table = jnp.asarray(rng.permutation(np.arange(1, N))[:S * P]
                        .reshape(S, P), jnp.int32)
    ends = jnp.asarray([3, 17, 30], jnp.int32)
    want = ao._paged_attention_reference(q, kp, vp, table, ends, 0.09,
                                         lambda x: x)
    rep = H // KVH
    grouped = jnp.transpose(q.reshape(S, rows, KVH, rep, dh),
                            (0, 2, 3, 1, 4)).reshape(S, H * rows, dh)
    got = pa.paged_attention(grouped, kp, vp, table, ends, sm_scale=0.09,
                             interpret=True, name='paged_block_attention')
    got = jnp.transpose(got.reshape(S, KVH, rep, rows, dh),
                        (0, 3, 1, 2, 4)).reshape(S, rows, H, dh)
    assert ref.rel_l2(np.asarray(got), np.asarray(want)) < 1e-5
    if rows == 1:
        plain = pa.paged_attention(q[:, 0], kp, vp, table, ends,
                                   sm_scale=0.09, interpret=True)
        assert np.array_equal(np.asarray(plain), np.asarray(got[:, 0]))


def test_block_attention_op_takes_the_kernel_under_the_flag(model):
    """Through the op's emitter: the regrouping in front of the kernel
    and behind it gives the reference lowering's rows back."""
    import jax.numpy as jnp
    from paddle_tpu.ops import attention_ops as ao

    class Op:
        def __init__(self, **io):
            self.io = io

        def single_input(self, k):
            return k

        def single_output(self, k):
            return k

        def attr(self, k, default=None):
            return {'sm_scale': 0.09}.get(k, default)

    class Ctx:
        mesh = None

        def __init__(self, **values):
            self.v = values

        def get(self, k):
            return self.v[k]

        def set(self, k, value):
            self.v[k] = value

    rng = np.random.default_rng(5)
    S, R, H, KVH, dh, pt, P, N = 2, 4, 4, 2, 128, 8, 3, 8
    vals = dict(
        Q=jnp.asarray(rng.standard_normal((S, R, H, dh)), jnp.float32),
        KPool=jnp.asarray(rng.standard_normal((N, pt, KVH, dh)),
                          jnp.float32),
        VPool=jnp.asarray(rng.standard_normal((N, pt, KVH, dh)),
                          jnp.float32),
        Table=jnp.asarray([[1, 2, 3], [4, 5, 6]], jnp.int32),
        Positions=jnp.asarray([11, 19], jnp.int32))
    outs = []
    for flag in (False, True):
        fluid.set_flags({'pallas_interpret': flag})
        try:
            ctx = Ctx(**vals)
            ao._paged_block_attention_emit(ctx, Op())
            outs.append(np.asarray(ctx.v['Out']))
        finally:
            fluid.set_flags({'pallas_interpret': False})
    assert ref.rel_l2(outs[1], outs[0]) < 1e-5


@pytest.mark.parametrize('rule', ref.RULES)
def test_unmask_op_is_the_reference_rule(rule):
    from paddle_tpu.ops.block_diffusion_ops import unmask
    rng = np.random.default_rng(11)
    d = ref.dims_of(model_of(remasking=rule, threshold=0.06))
    seen_over = seen_under = 0
    for trial in range(40):
        lg = rng.standard_normal((3, 4, d.vocab)).astype(np.float32) * 2
        ids = rng.integers(1, d.vocab - 1, size=(3, 4))
        ids[rng.random((3, 4)) < 0.6] = d.mask_id
        n = rng.integers(0, 4, size=3)
        out, left = unmask(lg, ids, n, d.mask_id, rule, d.threshold)
        for s in range(3):
            want = ref.unmask(lg[s], ids[s], n[s], d)
            assert list(np.asarray(out[s])) == want, (trial, s)
            assert int(left[s]) == want.count(d.mask_id)
            took = list(ids[s]).count(d.mask_id) - want.count(d.mask_id)
            seen_over += took > n[s]
            seen_under += took == n[s] > 0
    if rule == 'low_confidence_dynamic':
        assert seen_over and seen_under     # the threshold fires, and not
    else:
        assert not seen_over


def test_prefix_cache_hands_out_whole_blocks_only():
    pool = PagePool(20, 8)
    cache = PrefixCache(pool, block=4)
    prompt = list(range(1, 23))                    # 22 tokens
    table = PageTable(pool, 8)
    table.ensure(22)
    table.length = 22
    cache.register(prompt, table)                  # 2 pages and a tail of 4
    for extra in range(0, 7):
        child = prompt + list(range(100, 100 + extra))
        for limit in range(len(child) + 1):
            pages, shared = cache.match(child, limit=limit)
            assert shared % 4 == 0 and shared <= min(limit, 20), \
                (extra, limit, shared)
    assert cache.match(prompt, limit=22)[1] == 20
    loose = PrefixCache(PagePool(20, 8))           # the guard is the cache's
    t2 = PageTable(loose.pool, 8)
    t2.ensure(22)
    t2.length = 22
    loose.register(prompt, t2)
    assert loose.match(prompt + [7], limit=22)[1] == 22
    with pytest.raises(ValueError, match='whole blocks'):
        PrefixCache(PagePool(20, 6), block=4)


def test_the_predictor_opens_on_whole_blocks(model):
    pred, toks, _ = model
    dec = decoder(pred)
    assert dec.block_tokens == 4 and not dec.swappable
    assert dec.open_stream(0, toks[:22])['chunks'] == 3     # 20 tokens
    out = None
    while out is None:
        out = dec.prefill_step(0)
    assert (out.start, out.tail, out.chunk_ran) == (20, list(toks[20:22]),
                                                    True)
    assert dec.slot_tokens() == {0: 20}
    plan = dec.open_stream(1, list(toks[:22]) + [5])
    assert plan['shared_tokens'] == 20 and plan['chunks'] == 0
    out = dec.prefill_step(1, defer=True)
    assert (out.start, out.tail, out.chunk_ran) == (20, list(toks[20:22]) + [5],
                                                    False)
    short = dec.open_stream(2, toks[:3])
    assert short['chunks'] == 0
    assert dec.prefill_step(2).tail == list(toks[:3])


def test_what_steps_a_token_at_a_time_refuses_the_family_by_name(model,
                                                                   tmp_path):
    pred, toks, _ = model
    dec = decoder(pred)
    with pytest.raises(DecodeTranspileError, match='diffusion over blocks'):
        dec.decode_step(np.zeros(4, np.int64), np.zeros(4, np.int32))
    with pytest.raises(DecodeTranspileError, match='save_stream'):
        dec.save_stream(0)
    with pytest.raises(DecodeTranspileError, match='page shipping'):
        dec.export_prefix(toks[:16])
    with pytest.raises(DecodeTranspileError, match='page shipping'):
        dec.install_prefix(toks[:16], [], [])
    assert dec.resident_keys(toks[:16]) == []
    with pytest.raises(DecodeTranspileError, match='speculative'):
        pred.prepare_decoding(slots=2, paged=True, page_tokens=4,
                              speculative=True, spec_k=2, draft_layers=1)
    with pytest.raises(DecodeTranspileError, match='mesh serving'):
        pred.prepare_decoding(slots=2, paged=True, page_tokens=4, mesh='tp=2')
    with pytest.raises(ValueError, match='whole blocks'):
        pred.prepare_decoding(slots=2, paged=True, page_tokens=4,
                              prefill_chunk=6)
    with pytest.raises(DecodeTranspileError, match='whole blocks'):
        pred.prepare_decoding(slots=2, paged=True, page_tokens=6,
                              prefill_chunk=8)
