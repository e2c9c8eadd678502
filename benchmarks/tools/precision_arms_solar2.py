"""Where the Solar-Open2 block loses its digits to one bf16 pass: the
plain reference (reference/solar_open2.py) against itself at precision
"highest", with the backend's default matmul precision in ONE sublayer
kind at a time, everywhere, and everywhere with every token's experts
taken from the "highest" pass (so that a choice of 8 of 320 that rounding
turned cannot show); the bf16-stored control the same two ways. No
program runs: what is read here is the arithmetic's, whatever serves it.

    python benchmarks/tools/precision_arms_solar2.py \\
        --seeds 1,2 [--lengths 512,2048] [--rows 32] [--rehearse]

A line a seed and length: each arm's relative L2 of the logits of the
last --rows positions against "highest", the residual stream's after
each layer for the two whole-model arms, and the share of (token, layer)
pairs whose chosen experts differ from the "highest" pass's, and of
those that differ in an expert this share holds.
"""
import argparse
import functools
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(1, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

KINDS = ('kda', 'full_attention', 'experts', 'head')


def arms():
    """name -> (prec of each of KINDS, experts from the highest pass)."""
    hi, lo = 'float32', 'float32_default'
    out = {'all': ((lo,) * 4, False), 'all_routed_as_highest': ((lo,) * 4,
                                                                 True)}
    for i, kind in enumerate(KINDS):
        out[kind] = (tuple(lo if j == i else hi for j in range(4)), False)
    out['bfloat16'] = (('bfloat16',) * 4, False)
    out['bfloat16_routed_as_highest'] = (('bfloat16',) * 4, True)
    return out


def main(argv):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument('--seeds', required=True)
    ap.add_argument('--lengths', default='2048')
    ap.add_argument('--rows', type=int, default=32)
    ap.add_argument('--rehearse', action='store_true')
    args = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp
    import numpy as np
    from harness import manifest, runner
    from reference import solar_open2 as ref
    config = manifest.read_json(
        'benchmarks/configs/solar-open2-250b-serve.json')
    if args.rehearse:
        config = runner._overlaid(config, config['rehearse'])
    d = ref.dims_of(config)

    @functools.partial(jax.jit, static_argnums=(2, 3, 5, 6))
    def layer(base, i, kind, d, x, mixer_prec, expert_prec, routing):
        p = ref.layer_weights(base, i, kind, d)
        mixer = ref.kda_mixer if kind == 'kda' else ref.attention_mixer
        x = x + mixer(ref._rms(x, p['norm'], d.eps), p, d, mixer_prec)
        u = ref._rms(x, p['ffn_norm'], d.eps)
        chosen = ref.route(u, p, d)
        return x + ref.routed_part(
            u, p, d, expert_prec, lambda e: ref.expert_weights(base, i, e, d),
            routing=chosen if routing is None else routing) \
            + ref.shared_part(u, p, d, expert_prec), chosen

    def forward(base, tokens, precs, routings=None):
        by = dict(zip(KINDS, precs))
        x = ref._embed(base, d, tokens, by['head'])
        xs, chosen = [], []
        for i, kind in enumerate(d.kinds):
            x, c = layer(base, i, kind, d, x, by[kind], by['experts'],
                         None if routings is None else routings[i])
            xs.append(np.asarray(x[-args.rows:], np.float32))
            chosen.append((np.asarray(c[0]), np.asarray(c[1])))
        return np.asarray(ref._head(base, d, x[-args.rows:], by['head'])), \
            xs, chosen

    for seed in (int(s) for s in args.seeds.split(',')):
        base = ref.seed_key(seed)
        for n in (int(s) for s in args.lengths.split(',')):
            tokens = jnp.asarray(np.random.default_rng([seed, 13]).integers(
                1, d.vocab, size=n), jnp.int32)
            want, want_xs, routed = forward(base, tokens, ('float32',) * 4)
            routings = [tuple(jnp.asarray(a) for a in c) for c in routed]
            line = {'seed': seed, 'tokens': n, 'rows': args.rows}
            for name, (precs, pinned) in arms().items():
                got, xs, chosen = forward(base, tokens, precs,
                                          routings if pinned else None)
                line[name] = round(float(ref.rel_l2(got, want)), 6)
                if len(set(precs)) == 1:        # a whole-model arm
                    line[name + '.x_after_layer'] = [
                        round(float(ref.rel_l2(a, b)), 6)
                        for a, b in zip(xs, want_xs)]
                if name in ('all', 'bfloat16'):
                    sets = [(np.sort(c[0], -1) != np.sort(w[0], -1))
                            for c, w in zip(chosen, routed)]
                    held = [(((c[0] >= d.offset) & (c[0] < d.offset + d.held))
                             .sum(-1) != ((w[0] >= d.offset)
                                          & (w[0] < d.offset + d.held))
                             .sum(-1)) for c, w in zip(chosen, routed)]
                    line[name + '.tokens_rerouted'] = round(float(np.mean(
                        [s.any(-1).mean() for s in sets])), 4)
                    line[name + '.tokens_rerouted_in_held'] = round(float(
                        np.mean([h.mean() for h in held])), 4)
            print(json.dumps(line), flush=True)
    return 0


if __name__ == '__main__':
    sys.exit(main(sys.argv[1:]))
