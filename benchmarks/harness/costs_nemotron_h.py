"""Operations and bytes the Nemotron-H block
(paddle_tpu/models/nemotron_h.py) needs, from its shapes alone. `m` is a
configuration file's keys (HF nemotron_h names; `n_routed_experts` counts
the experts HELD, `router_experts` the published count the router
keeps); the layers run are the first `num_hidden_layers` letters of
`hybrid_override_pattern` (M mamba, E experts, * attention). Matmul
FLOPs count 2 per multiply-add; a causal product is counted at the half
the algorithm needs. Everything is float32 (4 bytes). Norms, gates and
the embedding lookup are left out.
"""
BYTES = 4


def kinds(m):
    return list(m['hybrid_override_pattern'][:int(m['num_hidden_layers'])])


def _mamba(m):
    """(heads H, head size P, groups G, state N, taps K, inner H P,
    convolved channels H P + 2 G N)."""
    h, p = int(m['mamba_num_heads']), int(m['mamba_head_dim'])
    g, n = int(m['n_groups']), int(m['ssm_state_size'])
    return h, p, g, n, int(m['conv_kernel']), h * p, h * p + 2 * g * n


def expert_params(m):
    """One routed expert: W1 [L, F] and W2 [F, L]."""
    return 2 * int(m['moe_latent_size']) * int(m['moe_intermediate_size'])


def layer_params(m, kind):
    """Parameters of one layer as held, its norm among them. M: the
    in-projection d (2 H P + 2 G N + H), the convolution (K + 1) (H P +
    2 G N), dt_bias, A_log and D 3 H, the gated norm H P, the
    out-projection H P d. E: the router d E and its bias E, the latent
    projections 2 d L, the shared expert 2 d S, and the experts held.
    *: q and o d H dh each, k and v d KVH dh each."""
    d = int(m['hidden_size'])
    if kind == 'M':
        h, _, _, _, k, inner, conv = _mamba(m)
        return (d * (inner + conv + h) + (k + 1) * conv + 3 * h + inner
                + inner * d + d)
    if kind == 'E':
        e = int(m.get('router_experts', m['n_routed_experts']))
        return (d * e + e + 2 * d * int(m['moe_latent_size'])
                + 2 * d * int(m['moe_shared_expert_intermediate_size'])
                + int(m['n_routed_experts']) * expert_params(m) + d)
    dh = int(m['head_dim'])
    return (2 * d * int(m['num_attention_heads']) * dh
            + 2 * d * int(m['num_key_value_heads']) * dh + d)


def param_count(m):
    """All parameters held: the layers run, the embedding and the head
    over the vocabulary served, and the final norm."""
    d, v = int(m['hidden_size']), int(m['vocab_size'])
    return sum(layer_params(m, k) for k in kinds(m)) + 2 * v * d + d


def weight_bytes(m):
    return BYTES * param_count(m)


def state_bytes_per_lane(m):
    """One lane's state in one mamba layer: H P N."""
    h, p, _, n, _, _, _ = _mamba(m)
    return BYTES * h * p * n


def conv_bytes_per_lane(m):
    """One lane's convolution rows in one mamba layer: (K - 1) (H P +
    2 G N)."""
    _, _, _, _, k, _, conv = _mamba(m)
    return BYTES * (k - 1) * conv


def ssm_state_bytes(m, slots):
    return kinds(m).count('M') * slots * (state_bytes_per_lane(m)
                                          + conv_bytes_per_lane(m))


def kv_bytes_per_token(m):
    """K and V of one token in the attention layers."""
    return BYTES * 2 * int(m['num_key_value_heads']) * int(m['head_dim']) \
        * kinds(m).count('*')


def ssd_step_bytes(m, lanes):
    """Bytes ONE ssd_step op has to move: each lane that takes part has
    its state read once and written once. (Its x, B, C and output, under
    a hundredth of it, and the convolution's rows, which another op
    moves, are left out.)"""
    return 2 * lanes * state_bytes_per_lane(m)


def ssd_step_flops(m, lanes):
    """h = a h + dx (x) B and y = h C: 2 + 2 FLOPs a state element."""
    h, p, _, n, _, _, _ = _mamba(m)
    return 4 * lanes * h * p * n


def ssd_chunk_flops(m, tokens, block=None):
    """FLOPs ONE ssd_chunk op needs for `tokens` tokens, in blocks of
    `block` (the published chunk_size; arXiv:2405.21060). A token: C B^T
    lower, block N a group; its product with dt x lower, block P a
    head; C h and the state's B^T (dt x), 2 N P a head each."""
    h, p, g, n, _, _, _ = _mamba(m)
    block = int(block or m['chunk_size'])
    return tokens * (block * n * g + block * p * h + 4 * n * p * h)


def ssd_chunk_bytes(m, tokens):
    """Bytes ONE ssd_chunk op has to move: x, B, C in and y out for each
    token, the state read once and written once."""
    h, p, g, n, _, _, _ = _mamba(m)
    return BYTES * tokens * (2 * h * p + 2 * g * n) \
        + 2 * state_bytes_per_lane(m)


def expert_bytes(m, experts_touched):
    """Bytes ONE moe_experts op has to read: W1 and W2 of each held
    expert that at least one of its rows chose."""
    return BYTES * experts_touched * expert_params(m)


def expert_flops(m, pairs):
    """FLOPs ONE moe_experts op needs: both products for each pair of
    row and held expert."""
    return 2 * pairs * expert_params(m)


def paged_attention_bytes(m, live_tokens):
    """Bytes ONE paged_attention op has to read: K and V of every live
    token, each once whatever the number of query heads."""
    return live_tokens * kv_bytes_per_token(m) // max(1, kinds(m).count('*'))


def decode_step_bytes(m, live_tokens, state_lanes, experts_touched):
    """Bytes one decode step HAS to move: every weight outside the
    routed experts once (the embedding's rows are a gather and are left
    out), W1 and W2 of the `experts_touched` experts a layer that the
    step's lanes chose among those held (a mean over the expert layers),
    the K and V of every live token in the attention layers, and for
    each lane that takes part its state and convolution rows, read and
    written, in every mamba layer."""
    d, v = int(m['hidden_size']), int(m['vocab_size'])
    n_e = kinds(m).count('E')
    dense = param_count(m) - v * d \
        - n_e * int(m['n_routed_experts']) * expert_params(m)
    return (BYTES * dense + n_e * expert_bytes(m, experts_touched)
            + live_tokens * kv_bytes_per_token(m)
            + 2 * state_lanes * kinds(m).count('M')
            * (state_bytes_per_lane(m) + conv_bytes_per_lane(m)))
