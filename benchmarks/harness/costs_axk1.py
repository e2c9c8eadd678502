"""Operations and bytes the A.X-K1 block (paddle_tpu/models/axk1.py)
needs, from its shapes alone. `m` is a configuration file's keys (HF
axk1 names; `n_routed_experts` counts the experts HELD,
`router_experts` the published count the router keeps). Matmul FLOPs
count 2 per multiply-add. Everything is float32 (4 bytes). Norms, the
rotary turn and the embedding lookup are left out.
"""
BYTES = 4


def _attn(m):
    """(heads H, dn, dr, dv, q rank, latent rank dc)."""
    return (int(m['num_attention_heads']), int(m['qk_nope_head_dim']),
            int(m['qk_rope_head_dim']), int(m['v_head_dim']),
            int(m['q_lora_rank']), int(m['kv_lora_rank']))


def layers(m):
    """(dense layers, expert layers) among those run."""
    dense = min(int(m['first_k_dense_replace']), int(m['num_hidden_layers']))
    return dense, int(m['num_hidden_layers']) - dense


def attention_params(m):
    """W_DQ d r_q, W_UQ r_q H (dn + dr), W_DKV d (dc + dr), W_UKV dc H
    (dn + dv), W_O H dv d, and the norms of c_Q and c_KV."""
    d = int(m['hidden_size'])
    h, dn, dr, dv, rq, dc = _attn(m)
    return (d * rq + rq * h * (dn + dr) + d * (dc + dr)
            + dc * h * (dn + dv) + h * dv * d + rq + dc)


def expert_params(m):
    """One routed expert (or the shared one): W1, W3 [d, F], W2 [F, d]."""
    return 3 * int(m['hidden_size']) * int(m['moe_intermediate_size'])


def layer_params(m, kind, held=None):
    """Parameters of one layer as held, its two norms among them.
    'dense': attention and a gated MLP 3 d F. 'experts': attention, the
    router d E and its bias E, the shared expert(s), and `held` routed
    experts (those the file holds where not given)."""
    d = int(m['hidden_size'])
    if kind == 'dense':
        return attention_params(m) + 3 * d * int(m['intermediate_size']) \
            + 2 * d
    e = int(m.get('router_experts', m['n_routed_experts']))
    held = int(m['n_routed_experts']) if held is None else held
    return (attention_params(m) + d * e + e
            + (int(m['n_shared_experts']) + held) * expert_params(m) + 2 * d)


def param_count(m):
    """All parameters held: the layers run, the embedding and the head
    over the vocabulary served, and the final norm."""
    d, v = int(m['hidden_size']), int(m['vocab_size'])
    dense, experts = layers(m)
    return (dense * layer_params(m, 'dense')
            + experts * layer_params(m, 'experts') + 2 * v * d + d)


def weight_bytes(m):
    return BYTES * param_count(m)


def latent_row_bytes(m):
    """What one token leaves in one layer: the normed latent and the
    rotated key, dc + dr values (as needed, not as stored)."""
    _, _, dr, _, _, dc = _attn(m)
    return BYTES * (dc + dr)


def stored_row_bytes(m):
    """The same row as the pool stores it: whole lanes of 128."""
    _, _, dr, _, _, dc = _attn(m)
    return BYTES * (-(-(dc + dr) // 128) * 128)


def latent_cache_bytes(m, pages, page_tokens):
    return pages * page_tokens * stored_row_bytes(m) \
        * int(m['num_hidden_layers'])


def mla_decode_bytes(m, rows):
    """Bytes the paged_latent_attention ops have to read for `rows`
    latent rows (a live token in one layer is one row): each once,
    whatever the number of heads."""
    return rows * latent_row_bytes(m)


def mla_decode_flops(m, rows):
    """Absorbed: every head's score over dc + dr and its sum over dc."""
    h, _, dr, _, _, dc = _attn(m)
    return 2 * rows * h * (2 * dc + dr)


def mla_prefill_flops(m, rows, context):
    """FLOPs ONE paged_latent_prefill op needs in the absorbed form for
    `rows` live query rows against `context` cached tokens: H heads a
    row, dc + dr a score and dc a sum. (The rows' own causal half, at
    most rows / 2 of a context of thousands, is counted whole.)"""
    h, _, dr, _, _, dc = _attn(m)
    return 2 * rows * h * context * (2 * dc + dr)


def mla_prefill_bytes(m, context):
    """Bytes ONE paged_latent_prefill op has to read: the context's
    latent rows once."""
    return context * latent_row_bytes(m)


def expert_bytes(m, experts_touched):
    """Bytes ONE moe_experts op has to read: the three matrices of each
    held expert that at least one of its rows chose."""
    return BYTES * experts_touched * expert_params(m)


def expert_flops(m, pairs):
    """FLOPs ONE moe_experts op needs: three products for each pair of
    row and held expert."""
    return 2 * pairs * expert_params(m)


def decode_step_bytes(m, latent_rows, experts_touched):
    """Bytes one decode step HAS to move: every weight outside the
    routed experts once (the embedding's rows are a gather and are left
    out), the three matrices of the `experts_touched` experts a layer
    that the step's lanes chose among those held (a mean over the
    expert layers), and the `latent_rows` rows its attention reads (over
    all layers)."""
    d, v = int(m['hidden_size']), int(m['vocab_size'])
    _, n_e = layers(m)
    outside = param_count(m) - v * d \
        - n_e * int(m['n_routed_experts']) * expert_params(m)
    return (BYTES * outside + n_e * expert_bytes(m, experts_touched)
            + mla_decode_bytes(m, latent_rows))
