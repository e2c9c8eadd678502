"""Speculative decoding over the paged KV cache: draft/verify with
bit-exact greedy acceptance.

The contract under test (ISSUE 14 acceptance):
- greedy speculative decode is token-for-token IDENTICAL
  (np.array_equal, not allclose) to plain greedy paged decode — for
  the real layer-truncated self-draft, for an always-right draft
  (every step emits k+1 tokens), for an always-wrong draft (every
  step degrades to exactly the plain step's one token), and for
  per-slot MIXED accept lengths inside a single verify iteration
- draft and verify each compile exactly once: the target executor
  holds 2 prepared programs (prefill + verify; plain decode only
  compiles if a fallback fires), the draft 2 (prefill + decode), and
  neither count grows across iterations
- mid-verify CacheExhaustedError rolls the whole speculation back
  (PR-12 deferred-unref discipline) and retries the iteration as ONE
  plain decode step, bit-exact, counting spec.fallback_steps
- two streams sharing a prefix page never cross-talk under
  speculation (COW isolation holds for multi-token appends)
- adaptive k narrows toward 1 under sustained rejection and recovers
  when the draft starts agreeing
- the ServingEngine spec path emits the same streams as the plain
  engine and surfaces spec accounting through stats()
"""
import numpy as np
import pytest

from paddle_tpu.models.transformer import TransformerConfig
from paddle_tpu.serving.paging import CacheExhaustedError
from test_paged import _save_lm

CFG = TransformerConfig(vocab=64, dim=32, heads=2, layers=2, ffn=64,
                        max_len=16, use_tp=False, use_sp=False)


@pytest.fixture(scope='module')
def lm_predictor(tmp_path_factory):
    return _save_lm(tmp_path_factory.mktemp('spec_lm'), CFG, 21)


def _plain(pred, slots=2, **kw):
    kw.setdefault('page_tokens', 4)
    kw.setdefault('prefill_chunk', CFG.max_len)
    return pred.prepare_decoding(slots=slots, **kw)


def _spec(pred, slots=2, spec_k=3, **kw):
    kw.setdefault('page_tokens', 4)
    kw.setdefault('prefill_chunk', CFG.max_len)
    return pred.prepare_decoding(slots=slots, speculative=True,
                                 spec_k=spec_k, draft_layers=1, **kw)


def _fake_chain(refs, prompt_len, wrong=False):
    """A deterministic stand-in for the draft chain: propose the
    plain-greedy continuation from `refs[slot]` verbatim (accept
    everything) or off-by-one tokens (reject everything). `wrong` may
    be a set of slots to make only those slots propose garbage —
    per-slot mixed accept lengths in one verify call."""
    def chain(live, tokens, positions, budget):
        out = {}
        for s in live:
            ref = refs[s]
            bad = wrong is True or (wrong is not False and s in wrong)
            props = []
            for j in range(budget[s]):
                idx = int(positions[s]) - prompt_len + 1 + j
                if idx >= len(ref):
                    break
                tok = int(ref[idx])
                props.append((tok + 1) % CFG.vocab if bad else tok)
            out[s] = props
        return out
    return chain


def _drive(spec, slot, first_id, pos, n):
    """Decode `n` tokens on one slot through spec_step, returning the
    emitted stream (first_id included) and the iteration count."""
    stream = [int(first_id)]
    toks = np.zeros((spec.slots,), np.int64)
    poss = np.zeros((spec.slots,), np.int32)
    steps = 0
    while len(stream) < n:
        toks[slot] = stream[-1]
        poss[slot] = pos
        out = spec.spec_step(toks, poss)
        steps += 1
        emitted = out[slot]
        stream.extend(int(t) for t in emitted)
        pos += len(emitted)
    return stream[:n], steps


# --------------------------------------------------------------------------
# bit-exact parity with the REAL self-draft, compile-once
# --------------------------------------------------------------------------

def test_spec_generate_bit_exact_and_compiles_once(lm_predictor):
    plain = _plain(lm_predictor)
    spec = _spec(lm_predictor)
    prompt = [3, 1, 4, 1, 5]
    n = CFG.max_len - len(prompt) - 1
    ref = plain.generate(prompt, n)
    got = spec.generate(prompt, n)
    assert np.array_equal(got, ref)
    st = spec.spec_stats()
    assert st['steps'] > 0 and st['draft_tokens'] > 0
    assert st['fallback_steps'] == 0
    assert (st['accepted_tokens'] + st['rejected_tokens']
            == st['draft_tokens'])
    # prefill + verify on the target; prefill + decode on the draft,
    # and with its first decode step the page copy program — page
    # tables, positions and COW pairs are feeds, never recompiles
    tstats = spec.jit_cache_stats()
    dstats = spec.draft.jit_cache_stats()
    assert tstats['prepared_programs'] == 2
    assert dstats['prepared_programs'] == 3
    got2 = spec.generate(prompt, n)       # a second full stream
    assert np.array_equal(got2, ref)
    assert spec.jit_cache_stats()['prepared_programs'] == 2
    assert spec.draft.jit_cache_stats()['prepared_programs'] == 3


# --------------------------------------------------------------------------
# acceptance rule corners: all-accept, all-reject, mixed per slot
# --------------------------------------------------------------------------

def test_all_accept_emits_k_plus_one_per_step(lm_predictor):
    plain = _plain(lm_predictor, slots=1)
    spec = _spec(lm_predictor, slots=1)
    prompt = [9, 2, 6, 5]
    n = CFG.max_len - len(prompt)
    ref = plain.generate(prompt, n)
    spec._draft_chain = _fake_chain({0: ref}, len(prompt))
    first = spec.prefill([prompt], [0])
    assert int(first[0]) == ref[0]
    stream, steps = _drive(spec, 0, first[0], len(prompt), n)
    assert stream == ref
    st = spec.spec_stats()
    assert st['accept_rate'] == 1.0
    # every iteration moved the stream by its full k+1 batch: far
    # fewer verify steps than tokens
    assert steps < (n - 1)
    assert st['effective_tokens_per_step'] > 1.0


def test_all_reject_degrades_to_plain_step_bit_exact(lm_predictor):
    plain = _plain(lm_predictor, slots=1)
    spec = _spec(lm_predictor, slots=1)
    prompt = [9, 2, 6, 5]
    n = CFG.max_len - len(prompt)
    ref = plain.generate(prompt, n)
    spec._draft_chain = _fake_chain({0: ref}, len(prompt), wrong=True)
    first = spec.prefill([prompt], [0])
    stream, steps = _drive(spec, 0, first[0], len(prompt), n)
    # every proposal rejected -> each step emits exactly the one token
    # the plain greedy path would have (the free verify bonus)
    assert stream == ref
    assert steps == n - 1
    st = spec.spec_stats()
    assert st['accept_rate'] == 0.0
    assert st['rejected_tokens'] == st['draft_tokens'] > 0


def test_mixed_per_slot_accepts_in_one_iteration(lm_predictor):
    plain = _plain(lm_predictor)
    spec = _spec(lm_predictor)
    pa, pb = [7, 3, 7, 4], [2, 9, 8, 1]
    n = CFG.max_len - 4 - 1
    ref_a = plain.generate(pa, n, slot=0)
    ref_b = plain.generate(pb, n, slot=1)
    # slot 0's draft is always right, slot 1's always wrong: ONE
    # spec_step must return a k+1-token batch and a 1-token batch
    spec._draft_chain = _fake_chain({0: ref_a, 1: ref_b}, 4,
                                    wrong={1})
    ia = spec.prefill([pa], [0])
    ib = spec.prefill([pb], [1])
    toks = np.array([int(ia[0]), int(ib[0])], np.int64)
    poss = np.array([4, 4], np.int32)
    out = spec.spec_step(toks, poss)
    assert len(out[0]) == spec.spec_k + 1
    assert len(out[1]) == 1
    sa = [int(ia[0])] + [int(t) for t in out[0]]
    sb = [int(ib[0])] + [int(t) for t in out[1]]
    poss = np.array([4 + len(out[0]), 4 + len(out[1])], np.int32)
    while min(len(sa), len(sb)) < n:
        for s, acc in ((0, sa), (1, sb)):
            if len(acc) >= n and s in spec._tables:
                spec.release(s)           # done: stop feeding it
        toks = np.array([sa[-1], sb[-1]], np.int64)
        out = spec.spec_step(toks, poss)
        for s, acc in ((0, sa), (1, sb)):
            emitted = out.get(s, ())
            acc.extend(int(t) for t in emitted)
            poss[s] += len(emitted)
    assert sa[:n] == ref_a and sb[:n] == ref_b


# --------------------------------------------------------------------------
# mid-verify exhaustion: rollback + plain-step retry, bit-exact
# --------------------------------------------------------------------------

def test_exhaustion_during_verify_falls_back_bit_exact(lm_predictor):
    # pool of 5 usable pages at pt=2: an 8-token prompt holds 4, a
    # plain step's ensure(9..10) fits in the 5th, but verify's
    # ensure(pos + k + 1) needs a 6th -> every spec iteration must
    # roll back its COWs/grows and retry as one plain decode step
    kw = dict(page_tokens=2, kv_pages=6)
    plain = _plain(lm_predictor, slots=1, **kw)
    spec = _spec(lm_predictor, slots=1, **kw)
    prompt = [1, 2, 3, 4, 5, 6, 7, 8]
    ia = plain.prefill([prompt], [0])
    ib = spec.prefill([prompt], [0])
    assert int(ib[0]) == int(ia[0])
    spec._draft_chain = lambda live, t, p, b: {s: [1, 1, 1]
                                               for s in live}
    toks = np.array([int(ia[0])], np.int64)
    poss = np.array([8], np.int32)
    for _ in range(2):
        ref = plain.decode_step(toks, poss)
        out = spec.spec_step(toks, poss)
        assert out[0] == [int(ref[0])]
        assert spec.pool_stats()['pages_in_use'] == \
            plain.pool_stats()['pages_in_use']
        toks = np.asarray(ref, np.int64)
        poss += 1
    assert spec.spec_stats()['fallback_steps'] == 2
    # when even the plain retry cannot grow, its typed error
    # propagates with the victim named (retryable -> the fleet sheds)
    poss[0] = 10
    with pytest.raises(CacheExhaustedError) as ei:
        spec.spec_step(toks, poss)
    assert ei.value.slots == (0,) and ei.value.retryable


# --------------------------------------------------------------------------
# COW prefix sharing under multi-token speculation
# --------------------------------------------------------------------------

def test_cow_shared_prefix_streams_never_cross_talk(lm_predictor):
    spec = _spec(lm_predictor)
    prompt = [7, 3, 7, 4, 2, 9]
    n = 6
    # the same stream alone, cold, on a plain pool of its own
    ref = _plain(lm_predictor, slots=1).generate(prompt, n)
    ia = spec.prefill([prompt], [0])      # cold: registers the prefix
    b = spec.open_stream(1, prompt)
    assert b['shared_tokens'] == 4        # adopted one full page
    ib = spec.prefill_step(1)
    assert int(ib) == int(ia[0]) == ref[0]
    sa, sb = [int(ia[0])], [int(ib)]
    poss = np.array([len(prompt), len(prompt)], np.int32)
    while min(len(sa), len(sb)) < n:
        toks = np.array([sa[-1], sb[-1]], np.int64)
        out = spec.spec_step(toks, poss)
        for s, acc in ((0, sa), (1, sb)):
            acc.extend(int(t) for t in out[s])
            poss[s] += len(out[s])
    # identical prompts: both streams must be exactly the isolated
    # stream — any COW leak across the shared page breaks one
    assert sa[:n] == ref and sb[:n] == ref


# --------------------------------------------------------------------------
# accept-rate-adaptive k
# --------------------------------------------------------------------------

def test_adaptive_k_narrows_and_recovers(lm_predictor):
    plain = _plain(lm_predictor, slots=1)
    spec = _spec(lm_predictor, slots=1)
    prompt = [9, 2, 6, 5]
    n = CFG.max_len - len(prompt)
    ref = plain.generate(prompt, n)
    assert spec.k_live == spec.spec_k
    spec._draft_chain = _fake_chain({0: ref}, len(prompt), wrong=True)
    for _ in range(6):                    # sustained rejection
        assert np.array_equal(spec.generate(prompt, n), ref)
    assert spec.k_live == 1
    spec._draft_chain = _fake_chain({0: ref}, len(prompt))
    for _ in range(8):                    # draft starts agreeing
        assert np.array_equal(spec.generate(prompt, n), ref)
    assert spec.k_live > 1


# --------------------------------------------------------------------------
# ServingEngine integration: parity + stats surface
# --------------------------------------------------------------------------

def test_engine_spec_parity_and_stats(lm_predictor):
    from paddle_tpu.serving import ServingEngine

    prompts = [[3, 1, 4], [1, 5, 9], [2, 6, 5], [3, 5, 8]]

    def run(dec):
        with ServingEngine(dec) as eng:
            reqs = [eng.submit(p, max_new_tokens=8) for p in prompts]
            toks = [r.result(120) for r in reqs]
            stats = eng.stats()
        return toks, stats

    ref, _ = run(_plain(lm_predictor, slots=4))
    got, stats = run(_spec(lm_predictor, slots=4))
    assert got == ref
    assert 'spec' in stats
    sp = stats['spec']
    assert sp['steps'] > 0 and 0.0 <= sp['accept_rate'] <= 1.0
    assert stats['effective_tokens_per_step'] > 0.0
