"""Operations and bytes the hybrid block (paddle_tpu/models/hybrid.py)
needs, from its shapes alone. `m` is a configuration file's keys (HF
olmo_hybrid names); the layers run are the first `num_hidden_layers` of
`layer_types`. Matmul FLOPs count 2 per multiply-add; a causal product
is counted at the half the algorithm needs. Everything is float32
(4 bytes). Norms, gates and the embedding lookup are left out.
"""
BYTES = 4


def _dims(m):
    return (int(m['hidden_size']), int(m['num_attention_heads']),
            int(m['linear_key_head_dim']), int(m['linear_value_head_dim']),
            int(m['intermediate_size']), int(m['vocab_size']),
            int(m['linear_conv_kernel_dim']))


def kinds(m):
    return list(m['layer_types'][:int(m['num_hidden_layers'])])


def layer_params(m, kind):
    """Parameters of one layer. Both kinds: the MLP 3 d f and two norms
    2 d. linear_attention: q, k d H dk each, v d H dv, the convolution
    K H (2 dk + dv), the write-strength and decay projections 2 d H,
    A_log and dt_bias 2 H, the output gate d H dv, the head norm dv, the
    output projection H dv d. full_attention: q, k, v, o d d each and
    two norms 2 d."""
    d, h, dk, dv, f, _, k = _dims(m)
    mlp = 3 * d * f + 2 * d
    if kind == 'linear_attention':
        return (2 * d * h * dk + d * h * dv + k * h * (2 * dk + dv)
                + 2 * d * h + 2 * h + d * h * dv + dv + h * dv * d + mlp)
    return 4 * d * d + 2 * d + mlp


def param_count(m):
    """All parameters held: the layers run, the embedding, the final
    norm and the untied head."""
    d, _, _, _, _, v, _ = _dims(m)
    return sum(layer_params(m, k) for k in kinds(m)) + 2 * v * d + d


def weight_bytes(m):
    return BYTES * param_count(m)


def state_bytes_per_lane(m):
    """One lane's delta state in one linear_attention layer: H dk dv."""
    _, h, dk, dv, _, _, _ = _dims(m)
    return BYTES * h * dk * dv


def conv_bytes_per_lane(m):
    """One lane's convolution rows in one layer: (K - 1) H (2 dk + dv)."""
    _, h, dk, dv, _, _, k = _dims(m)
    return BYTES * (k - 1) * h * (2 * dk + dv)


def recurrent_state_bytes(m, slots):
    n = kinds(m).count('linear_attention')
    return n * slots * (state_bytes_per_lane(m) + conv_bytes_per_lane(m))


def kv_bytes_per_token(m):
    """K and V of one token in the full_attention layers."""
    d = _dims(m)[0]
    return BYTES * 2 * d * kinds(m).count('full_attention')


def gdn_step_bytes(m, lanes):
    """Bytes ONE gated_delta_step op has to move: each lane that takes
    part has its state read once and written once. (Its q, k, v and
    output, 4 H (dk + dv) bytes a lane, and the convolution's rows, which
    another op moves, are left out: under a thousandth and a sixteenth
    of it.)"""
    return 2 * lanes * state_bytes_per_lane(m)


def gdn_chunk_flops(m, tokens, block=64):
    """FLOPs ONE gated_delta_chunk op needs for `tokens` tokens, all
    heads, in blocks of `block` (arXiv:2406.06484). A token, a head:
    k k^T strictly lower and q k^T lower, block dk each; the triangular
    solve for W and U, block (dk + dv); attn v', block dv; W S, q S and
    k^T v', 2 dk dv each."""
    _, h, dk, dv, _, _, _ = _dims(m)
    per_token = 2 * block * dk + block * (dk + dv) + block * dv \
        + 3 * 2 * dk * dv
    return tokens * h * per_token


def gdn_chunk_bytes(m, tokens):
    """Bytes ONE gated_delta_chunk op has to move: q, k, v in and the
    output out for each token, the state read once and written once."""
    _, h, dk, dv, _, _, _ = _dims(m)
    return BYTES * tokens * h * (2 * dk + 2 * dv) \
        + 2 * state_bytes_per_lane(m)


def decode_step_bytes(m, live_tokens, state_lanes):
    """Bytes one decode step HAS to move: every weight once (the
    embedding's rows are a gather and are left out), the K and V of every
    live token in the full_attention layers, and for each lane that takes
    part its delta state and convolution rows, read and written, in every
    linear_attention layer."""
    d, _, _, _, _, v, _ = _dims(m)
    n_lin = kinds(m).count('linear_attention')
    return (weight_bytes(m) - BYTES * v * d
            + live_tokens * kv_bytes_per_token(m)
            + 2 * state_lanes * n_lin
            * (state_bytes_per_lane(m) + conv_bytes_per_lane(m)))
