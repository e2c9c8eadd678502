"""Traffic over a fixed corpus of long documents: every request is one
of the deployment's documents followed by a fresh question. The corpus
(`documents`) comes from the mix's own `doc_seed`, so it is the same in
every run and a builder can cache it during set-up from the mix's
`params` alone; --seed decides the order of arrival, which gap goes
where and the questions' token ids, and nothing else: the multiset of
(document, question length, output length) triples is one fixed design
(`triples`), the same for every seed.
"""
from __future__ import annotations

import numpy as np

from . import sampling

PAGE = 16       # document lengths are whole pages of the serving path


def documents(params, model):
    """The corpus: `n_documents` token arrays, their lengths evenly
    spaced over `document_tokens` [lo, hi] in multiples of PAGE, their
    ids drawn from `doc_seed` over the vocabulary served."""
    lo, hi = params['document_tokens']
    n = int(params['n_documents'])
    lengths = np.rint(np.linspace(lo, hi, n) / PAGE).astype(np.int64) * PAGE
    vocab = int(model['vocab_size'])
    return [sampling.rng_of(params['doc_seed'], 100 + i).integers(
        1, vocab, size=int(k), dtype=np.int64)
        for i, k in enumerate(lengths)]


def triples(params, n):
    """The n (document, question length, output length) triples of a
    part, in design order. Request i asks about document i mod D, so the
    documents' counts differ by at most one; question and output lengths
    are the quantiles of their log-uniform laws, and within each round
    of D requests a document takes the place (a d + b r) mod D of the
    round's sorted lengths, a and b odd: every document meets short and
    long questions and answers alike, in every seed."""
    docs = int(params['n_documents'])
    q_lo, q_hi = params['question_tokens']
    o_lo, o_hi = params['output_tokens']
    questions = sampling.log_uniform_lengths(q_lo, q_hi, n)
    outputs = sampling.log_uniform_lengths(o_lo, o_hi, n)
    out = []
    for start in range(0, n, docs):
        m, r = min(docs, n - start), start // docs

        def place(a, b):
            return np.argsort(np.argsort(
                [(a * d + b * r) % docs for d in range(m)]))

        q_at, o_at = place(7, 5), place(5, 3)
        out.extend((d, int(questions[start + q_at[d]]),
                    int(outputs[start + o_at[d]])) for d in range(m))
    return out


def doc_followup(params, seed, model, seconds):
    """Plan for the open-loop drive, as harness/traffic.open_loop makes
    it: round(rate * seconds) judged requests due inside the window at
    exponential gaps, then as many again, unjudged."""
    rate = float(params['rate_rps'])
    n = max(1, int(round(rate * seconds)))
    docs = documents(params, model)
    vocab = int(model['vocab_size'])
    context = int(model['n_positions'])
    plan = {'judged': n, 'timeout_s': float(params['timeout_s']),
            'requests': []}
    start = 0.0
    for stream in (2, 3):
        rng = sampling.rng_of(seed, stream + 10)
        gaps = sampling.shuffled(sampling.exponential_gaps(rate, n), rng)
        due = start + np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
        start += float(gaps.sum())
        order = rng.permutation(n)
        design = triples(params, n)
        for i, t in zip(order, due):
            d, q, o = design[i]
            q = min(q, context - o - len(docs[d]))
            plan['requests'].append({
                'prompt': np.concatenate([docs[d], rng.integers(
                    1, vocab, size=q, dtype=np.int64)]),
                'max_new': o, 'due': float(t), 'document': d})
    return plan
