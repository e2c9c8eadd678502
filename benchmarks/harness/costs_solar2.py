"""Operations and bytes the Solar-Open2 block
(paddle_tpu/models/solar_open2.py) needs, from its shapes alone. `m` is
a configuration file's keys (HF solar_open2 names; `n_routed_experts`
counts the experts HELD, `router_experts` the published count the router
keeps); layer i of the `num_hidden_layers` run is softmax attention
where `gqa_layers` lists it and the delta rule with a decay a key
channel (kda) otherwise, each followed by an expert sublayer. Everything
is float32 (4 bytes). Matmul FLOPs count 2 per multiply-add; a causal
product is counted at the half the algorithm needs. Norm gains are
counted with their layer; the embedding's rows are a gather and are
left out of a step's bytes; the untied head is read whole every step.
"""
BYTES = 4


def kinds(m):
    gqa = set(int(i) for i in m['gqa_layers'])
    return ['full_attention' if i in gqa else 'kda'
            for i in range(int(m['num_hidden_layers']))]


def _kda(m):
    """(heads H, key size dk, value size dv, taps K, gate rank r)."""
    lin = m['linear_attn_config']
    dk = int(lin['head_dim'])
    return (int(lin['num_heads']), dk, dk,
            int(lin['short_conv_kernel_size']),
            int(m.get('kda_gate_rank', dk)))


def expert_params(m):
    """One routed expert (or the shared one): W1, W3 [d, F], W2 [F, d]."""
    return 3 * int(m['hidden_size']) * int(m['moe_intermediate_size'])


def mixer_params(m, kind):
    """A mixer with its norm. kda: q, k d H dk each and v d H dv, the
    convolution K H (2 dk + dv), the two low-rank gates d r + r H dk and
    d r + r H dv, the write strengths d H, A_log H, dt_bias H dk, the
    head norm dv, the output projection H dv d. full_attention: q, the
    output gate and o d H dh each, k and v d KVH dh each."""
    d = int(m['hidden_size'])
    if kind == 'kda':
        h, dk, dv, k, r = _kda(m)
        return (d * h * (2 * dk + dv) + k * h * (2 * dk + dv)
                + 2 * d * r + r * h * (dk + dv) + d * h + h + h * dk + dv
                + h * dv * d + d)
    heads, dh = int(m['num_attention_heads']), int(m['head_dim'])
    return 3 * d * heads * dh \
        + 2 * d * int(m['num_key_value_heads']) * dh + d


def sublayer_params(m, held=None):
    """An expert sublayer with its norm outside its routed experts (the
    router d E and its selection bias E, the shared experts), and
    `held` routed experts (those the file holds where not given)."""
    d = int(m['hidden_size'])
    e = int(m.get('router_experts', m['n_routed_experts']))
    held = int(m['n_routed_experts']) if held is None else held
    return (d * e + e + d
            + (int(m['n_shared_experts']) + held) * expert_params(m))


def param_count(m):
    """All parameters held: the layers run, the embedding and the head
    over the vocabulary served, and the final norm."""
    d, v = int(m['hidden_size']), int(m['vocab_size'])
    return sum(mixer_params(m, k) + sublayer_params(m) for k in kinds(m)) \
        + 2 * v * d + d


def weight_bytes(m):
    return BYTES * param_count(m)


def state_bytes_per_lane(m):
    """One lane's delta state in ONE kda layer: H dk dv."""
    h, dk, dv, _, _ = _kda(m)
    return BYTES * h * dk * dv


def conv_bytes_per_lane(m):
    """One lane's convolution rows in one kda layer: (K - 1) H (2 dk +
    dv)."""
    h, dk, dv, k, _ = _kda(m)
    return BYTES * (k - 1) * h * (2 * dk + dv)


def snapshot_row_bytes(m):
    """One snapshot row, which is one slot's recurrent state: every kda
    layer's delta state and convolution rows."""
    return kinds(m).count('kda') * (state_bytes_per_lane(m)
                                    + conv_bytes_per_lane(m))


def recurrent_state_bytes(m, slots):
    return slots * snapshot_row_bytes(m)


def state_copy_bytes(m):
    """Bytes ONE run of a state copy program has to move: a row read and
    a row written."""
    return 2 * snapshot_row_bytes(m)


def kv_bytes_per_token(m):
    """K and V of one token in the attention layers."""
    return BYTES * 2 * int(m['num_key_value_heads']) * int(m['head_dim']) \
        * kinds(m).count('full_attention')


def kda_step_bytes(m, lanes):
    """Bytes ONE kda_step op has to move: each lane that takes part has
    its state read once and written once. (Its q, k, decay, v and
    output, 4 H (3 dk + 2 dv) bytes a lane, and the convolution's rows,
    which another op moves, are left out: under a thousandth and a
    fourteenth of it.)"""
    return 2 * lanes * state_bytes_per_lane(m)


def kda_chunk_flops(m, tokens, block=64):
    """FLOPs ONE kda_chunk op needs for `tokens` tokens, all heads, in
    blocks of `block`. A token, a head: the decayed k k^T strictly lower
    and q k^T lower, block dk each (inside a sub-block on the vector
    unit, across sub-blocks as matrix products: the same count); the
    triangular solve for W and U, block (dk + dv); A v', block dv; W S,
    q S and k^T v', 2 dk dv each. The decays' exponentials and scalings
    are elementwise and left out."""
    h, dk, dv, _, _ = _kda(m)
    per_token = 2 * block * dk + block * (dk + dv) + block * dv \
        + 3 * 2 * dk * dv
    return tokens * h * per_token


def kda_chunk_bytes(m, tokens):
    """Bytes ONE kda_chunk op has to move: q, k, v and the decays in and
    the output out for each token, the state read once and written
    once."""
    h, dk, dv, _, _ = _kda(m)
    return BYTES * tokens * h * (3 * dk + 2 * dv) \
        + 2 * state_bytes_per_lane(m)


def expert_bytes(m, experts_touched):
    """Bytes ONE moe_experts op has to read: the three matrices of each
    held expert that at least one of its rows chose."""
    return BYTES * experts_touched * expert_params(m)


def expert_flops(m, pairs):
    """FLOPs ONE moe_experts op needs: three products for each pair of
    row and held expert."""
    return 2 * pairs * expert_params(m)


def kda_weight_bytes(m):
    """The kda mixers' own weights, all kda layers: what a decode step
    reads of them beside their state."""
    return BYTES * kinds(m).count('kda') * mixer_params(m, 'kda')


def decode_step_bytes(m, live_tokens, state_lanes, experts_touched):
    """Bytes one decode step HAS to move: every weight outside the
    routed experts once (the head among them, the embedding not: a
    gather), the three matrices of the `experts_touched` experts a layer
    that the step's lanes chose among those held (a mean over the
    layers), the K and V of every live token in the attention layers,
    and for each lane that takes part its delta state and convolution
    rows, read and written, in every kda layer."""
    d, v = int(m['hidden_size']), int(m['vocab_size'])
    layers = len(kinds(m))
    dense = param_count(m) - v * d \
        - layers * int(m['n_routed_experts']) * expert_params(m)
    return (BYTES * (dense + layers * experts_touched * expert_params(m))
            + live_tokens * kv_bytes_per_token(m)
            + 2 * state_lanes * snapshot_row_bytes(m))
