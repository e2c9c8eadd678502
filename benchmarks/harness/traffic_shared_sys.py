"""Single-turn chat behind one of a few shared system prompts: users of
an assistant's public chat endpoint. Every request is one of the
deployment's system prompts followed by a fresh user message, and asks
for a worked answer of several hundred tokens; nothing else is shared.

The system prompts are harness/traffic_sessions.system_prompts (whole
pages, ids from the mix's own `sys_seed`: the deployment's, the same in
every run, so a builder can prefill them in set-up from the mix's
`params` alone). The multiset of (system prompt, message length, output
length) is one fixed design for every seed: harness/traffic_docs.triples
with a system prompt where it has a document, so the prompts' counts
differ by at most one and every prompt meets short and long messages
and answers alike. --seed decides the order of arrival, which gap goes
where and the messages' token ids, and every part of the plan (the part
the pre-roll ends, the judged window, the tail behind it) replays the
seed's one deal of order and gaps with token ids of its own, so a
window holds the same work under every seed. A plan holds

    warm      the system prompts: set-up prefills each alone, cache only,
              so that each has its pages and its snapshot
    preroll   the requests due in the `preroll_s` seconds before the
              window: set-up submits them when due and leaves them running
    requests  those due from the window's start on, `due` counted from
              there: `judged` of them inside the window, then the tail
"""
from __future__ import annotations

import numpy as np

from . import sampling, traffic_docs, traffic_sessions


def design(params, n):
    """The n (system prompt, message length, output length) triples of a
    part, in design order."""
    return traffic_docs.triples(
        {'n_documents': params['n_system_prompts'],
         'question_tokens': params['user_tokens'],
         'output_tokens': params['output_tokens']}, n)


def chat_shared_sys(params, seed, model, seconds):
    """Plan for the open-loop drive (harness/drives.open_loop reads
    `requests`, `judged` and `timeout_s`); `warm` and `preroll` are the
    builder's (builders/granite_h.ServeSystem.warm_up)."""
    rate = float(params['rate_rps'])
    # the whole requests a part holds at this rate: none is due past its
    # part's end (the rate times a window need not be a whole number)
    n = max(1, int(rate * seconds + 1e-9))
    preroll = float(params.get('preroll_s', 0))
    if preroll > seconds:
        raise ValueError('a pre-roll of %g s is longer than a part of %g s'
                         % (preroll, seconds))
    system = traffic_sessions.system_prompts(params, model)
    vocab = int(model['vocab_size'])
    context = int(model['n_positions'])
    # the seed's one deal, replayed in every part
    deal = sampling.rng_of(seed, 12)
    gaps = sampling.shuffled(sampling.exponential_gaps(rate, n), deal)
    offsets = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    order = deal.permutation(n)
    triples = design(params, n)
    requests = []
    # the part the window's start ends, the window, the tail
    for part, stream in ((-1, 1), (0, 2), (1, 3)):
        rng = sampling.rng_of(seed, stream + 30)        # this part's ids
        for i, offset in zip(order, offsets):
            s, u, o = triples[i]
            if len(system[s]) + u + o > context:
                raise ValueError('a request of %d tokens passes '
                                 'n_positions %d'
                                 % (len(system[s]) + u + o, context))
            requests.append({
                'prompt': np.concatenate([system[s], rng.integers(
                    1, vocab, size=u, dtype=np.int64)]),
                'max_new': o, 'due': part * seconds + float(offset),
                'system': s})
    return {'judged': n, 'timeout_s': float(params['timeout_s']),
            'warm': system,
            'preroll': [dict(r, due=r['due'] + preroll) for r in requests
                        if -preroll <= r['due'] < 0],
            'requests': [r for r in requests if r['due'] >= 0]}
