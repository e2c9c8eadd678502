"""Operations and bytes a GPT-2 block model needs, from its shapes alone.

`m` is a configuration's `model` group (HF GPT-2 keys). Matmul FLOPs
count 2 per multiply-add. Causal attention is counted at half of the
full T x T products: that is what the algorithm needs. Recomputed work
(the flash backward's re-run forward, remat) is never counted.
Elementwise work, LayerNorm, softmax and the embedding lookup are left
out: they are not matmul work and the peak is the matmul unit's.
"""


def _dims(m):
    return (int(m['n_embd']), int(m['n_layer']), int(m['n_head']),
            int(m['n_inner']), int(m['vocab_size']))


def param_count(m):
    d, n_layer, _, f, v = _dims(m)
    per_layer = (d * 3 * d + 3 * d) + (d * d + d) + (d * f + f) \
        + (f * d + d) + 4 * d
    return (v * d + int(m['n_positions']) * d + n_layer * per_layer
            + 2 * d + d * v + v)


def dense_flops_per_token(m):
    """Forward matmul FLOPs per token outside attention's T x T part:
    qkv, proj, up, down in every layer, and the head."""
    d, n_layer, _, f, v = _dims(m)
    return 2 * (n_layer * (3 * d * d + d * d + 2 * d * f) + d * v)


def attn_flops_fwd(m, t, causal=True):
    """Forward FLOPs of QK^T and PV for ONE sequence of t tokens in ONE
    layer, all heads: 2 products x 2 x t x t x n_embd, halved when
    causal."""
    d = int(m['n_embd'])
    full = 2 * 2 * t * t * d
    return full // 2 if causal else full


def train_flops_per_token(m, t):
    """Model FLOPs per trained token at sequence length t: forward +
    backward = 3 x forward (each matmul has two backward products)."""
    n_layer = int(m['n_layer'])
    fwd = dense_flops_per_token(m) + n_layer * attn_flops_fwd(m, t) / t
    return 3 * fwd


def flash_flops_per_sequence(m, t):
    """Required FLOPs of the attention kernels for one sequence, all
    layers: forward (2 products) + backward (4 products: dV, dP, dQ, dK;
    the backward's re-run of QK^T is recomputation and is not counted)
    = 3 x forward."""
    return 3 * int(m['n_layer']) * attn_flops_fwd(m, t)


def decode_step_bytes(m, live_tokens, weight_bytes=4, kv_bytes=4):
    """Bytes one decode step HAS to read: every weight once (the
    embedding and position tables are gathers and are left out) and the
    K and V of every live token."""
    d, n_layer, _, f, v = _dims(m)
    weights = n_layer * (4 * d * d + 2 * d * f + 9 * d + f) \
        + 2 * d + d * v + v
    kv = live_tokens * n_layer * 2 * d
    return weights * weight_bytes + kv * kv_bytes


def kv_bytes_per_token(m, kv_bytes=4):
    return int(m['n_layer']) * 2 * int(m['n_embd']) * kv_bytes
