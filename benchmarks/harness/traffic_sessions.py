"""Traffic of multi-turn chat sessions: people talking to an assistant
(or an agent calling a model) behind one of a few shared system prompts.
Every turn resends the whole conversation, and the server is expected to
remember it: turn k's prompt is the system prompt, the user and
assistant tokens of turns 1..k-1 and a new user message. The earlier
answers are SCRIPTED, a replayed trace as Mooncake's are, so a prompt
never depends on what seeded weights generated: turn k asks for
`max_new` tokens, and turn k + 1 carries a scripted answer of that
length in their place.

Sessions START at exponential gaps, at `rate_rps` / (mean turns a
session), as harness/traffic.open_loop's requests arrive: time is cut
into parts of the window's length, one of them the window, and a part
holds round(rate x seconds) starts whose gaps are the quantiles of the
exponential law (sampling.exponential_gaps) in a seeded order. A
session's next turn is due `answer_s_per_token` x the answer's tokens +
a think time of `think_s` after the last, so turns arrive at `rate_rps`
on the whole and in bursts, and a session outlasts its part (up to 51 s
of a 45 s part). Every part REPLAYS the seed's one deal of the design
(the order of the gaps and of the sessions, which lengths meet in a
turn) with token ids of its own: the turns that reach into a part from
the one before are those that leave it for the next, so the window
holds the design's turns, round(rate x seconds) sessions' worth (321 at
7.2 turns/s and 45 s), under every seed, as a window of
harness/traffic.open_loop holds round(rate x seconds) requests, and the
seed decides where in it the bursts fall. (With a deal of its own for
every part the window held 248 to 323 turns over 200 seeds, and
`tpot_p50_ms` followed the count: PERF.md section 6, PR 45.) Parts are
laid out from before the longest session could have begun ahead of time
0, where set-up's pre-roll begins, to the end of the tail behind the
window. A plan holds

    warm      prompts set-up prefills, cache only, before the pre-roll:
              the system prompts, then, for each session already in
              progress at time 0, the prompt of its last turn before it
    preroll   the turns due in [0, preroll_s): set-up submits them when
              due and leaves them running
    requests  the turns due from preroll_s on, `due` counted from there:
              `judged` of them inside the window, then the tail

The system prompts come from the mix's own `sys_seed`: the deployment's,
the same in every run. A part's sessions (system prompt, turns) and its
multisets of gaps, message, answer and think lengths are one fixed
design for every seed (quantile grids, harness/sampling.py); --seed
decides which gap goes where, the order in which the sessions arrive,
which lengths meet in a turn, and the token ids of messages and answers.
"""
from __future__ import annotations

import math

import numpy as np

from . import sampling

PAGE = 16       # system prompts are whole pages of the serving path


def system_prompts(params, model):
    """The deployment's system prompts: `n_system_prompts` token arrays,
    lengths evenly spaced over `system_tokens` in multiples of PAGE, ids
    from `sys_seed` over the vocabulary served."""
    lo, hi = params['system_tokens']
    n = int(params['n_system_prompts'])
    lengths = np.rint(np.linspace(lo, hi, n) / PAGE).astype(np.int64) * PAGE
    vocab = int(model['vocab_size'])
    return [sampling.rng_of(params['sys_seed'], 100 + i).integers(
        1, vocab, size=int(k), dtype=np.int64)
        for i, k in enumerate(lengths)]


def span_s(params):
    """The longest a session can last, first turn to last."""
    return (max(params['turns']) - 1) * (
        float(params['answer_s_per_token']) * params['answer_tokens'][1]
        + params['think_s'][1])


def session_rate(params):
    """Sessions a second: turns a second over the mean turns of one."""
    kinds = params['turns']
    return float(params['rate_rps']) * len(kinds) / sum(kinds)


def design(params, seconds):
    """The fixed part of a plan: {'t0', 'sessions', 'gaps', 'user',
    'answer', 'think'}. `t0` are the starts of the parts of `seconds`,
    from the first part a session of the pre-roll can have begun in to
    the tail; every part holds the same round(session rate x seconds)
    sessions, (system prompt, turns) dealt in a round, the gaps between
    their starts (the exponential law's quantiles, summing to
    `seconds`), and for its sessions' turns the user-message and answer
    lengths (log-uniform) and the think times (uniform), each a
    quantile grid."""
    kinds = [int(t) for t in params['turns']]
    prompts = int(params['n_system_prompts'])
    preroll = float(params.get('preroll_s', 0))
    n = max(1, int(round(session_rate(params) * seconds)))
    back = math.ceil((span_s(params) + preroll) / seconds)
    lo, hi = params['think_s']
    sessions = [(j % prompts, kinds[(j // prompts) % len(kinds)])
                for j in range(n)]
    turns = sum(k for _, k in sessions)
    return {'t0': [preroll + part * seconds for part in range(-back, 2)],
            'sessions': sessions,
            'gaps': sampling.exponential_gaps(n / seconds, n),
            'user': sampling.log_uniform_lengths(
                *params['user_tokens'], turns),
            'answer': sampling.log_uniform_lengths(
                *params['answer_tokens'], turns),
            'think': lo + (hi - lo) * sampling.quantile_grid(turns)}


def chat_sessions(params, seed, model, seconds):
    """Plan for the open-loop drive (harness/drives.open_loop reads
    `requests`, `judged` and `timeout_s`); `warm` and `preroll` are the
    builder's (builders/granite_h.ServeSystem.warm_up)."""
    rng = sampling.rng_of(seed, 12)
    vocab = int(model['vocab_size'])
    context = int(model['n_positions'])
    preroll = float(params.get('preroll_s', 0))
    per_token = float(params['answer_s_per_token'])
    system = system_prompts(params, model)
    turns, warm, session = [], [], 0
    plan = design(params, seconds)
    # the seed's one deal, replayed in every part
    offsets = np.concatenate(
        [[0.0], np.cumsum(sampling.shuffled(plan['gaps'], rng))[:-1]])
    order = rng.permutation(len(plan['sessions']))
    user, answer, think = (sampling.shuffled(plan[k], rng)
                           for k in ('user', 'answer', 'think'))
    for t0 in plan['t0']:
        at = 0
        for offset, j in zip(offsets, order):
            which, n_turns = plan['sessions'][j]
            # `since` the part's start, the same in every part
            history, since, last_before = system[which], float(offset), None
            for k in range(n_turns):
                u, a = int(user[at]), int(answer[at])
                prompt = np.concatenate([history, rng.integers(
                    1, vocab, size=u, dtype=np.int64)])
                if len(prompt) + a > context:
                    raise ValueError('a session of %d tokens passes '
                                     'n_positions %d'
                                     % (len(prompt) + a, context))
                turn = {'prompt': prompt, 'max_new': a, 'due': t0 + since,
                        'session': session, 'turn': k, 'system': which}
                if turn['due'] < 0:
                    last_before = turn
                else:
                    if last_before is not None:
                        warm.append(last_before['prompt'])
                        last_before = None
                    turns.append(turn)
                history = np.concatenate([prompt, rng.integers(
                    1, vocab, size=a, dtype=np.int64)])
                since += per_token * a + float(think[at])
                at += 1
            session += 1
    turns.sort(key=lambda t: t['due'])
    pre = [t for t in turns if t['due'] < preroll]
    rest = [dict(t, due=t['due'] - preroll) for t in turns
            if preroll <= t['due'] < preroll + 2 * seconds]
    return {'judged': sum(t['due'] < seconds for t in rest),
            'timeout_s': float(params['timeout_s']),
            'requests': rest, 'preroll': pre, 'warm': system + warm}
