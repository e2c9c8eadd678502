"""The FLOP and byte counts against a hand count at the published
widths, L12 (the training cut) and L24."""
import pytest

from harness import costs, manifest, peaks

D, F, V, T, H = 2048, 8192, 50257, 2048, 16


def _cfg(n_layer):
    return {'n_embd': D, 'n_layer': n_layer, 'n_head': H, 'n_inner': F,
            'vocab_size': V, 'n_positions': T}


@pytest.mark.parametrize('n_layer,params', [(12, 814_400_593),
                                            (24, 1_418_699_857)])
def test_param_count(n_layer, params):
    # per layer: qkv 2048*6144+6144, proj 2048*2048+2048, up 2048*8192+8192,
    # down 8192*2048+2048, two LayerNorms 4*2048
    assert 12_589_056 + 4_196_352 + 16_785_408 + 16_779_264 + 8_192 \
        == 50_358_272
    hand = V * D + T * D + n_layer * 50_358_272 + 2 * D + D * V + V
    assert costs.param_count(_cfg(n_layer)) == hand == params


@pytest.mark.parametrize('n_layer', [12, 24])
def test_train_flops_per_token(n_layer):
    # dense, forward, per token: 2 FLOPs x (4 d^2 + 2 d f) per layer + 2 d V
    dense = 2 * (n_layer * (4 * D * D + 2 * D * F) + D * V)
    assert costs.dense_flops_per_token(_cfg(n_layer)) == dense
    # attention, forward, per token at T: QK^T and PV are 2 x 2 x T x d
    # each for the full square, half of it under the causal mask
    attn = n_layer * (2 * 2 * T * D) / 2
    assert costs.train_flops_per_token(_cfg(n_layer), T) \
        == pytest.approx(3 * (dense + attn), rel=1e-12)
    if n_layer == 12:
        # 3 x (2 x (12 x 50.33 M + 102.93 M) + 12 x 4.19 M) = 4.543 GFLOP
        assert costs.train_flops_per_token(_cfg(12), T) \
            == pytest.approx(4.5434e9, rel=1e-4)


def test_flash_flops_per_sequence():
    fwd_layer = 2 * 2 * T * T * D // 2            # 17.18 GFLOP
    assert costs.attn_flops_fwd(_cfg(12), T) == fwd_layer == 17_179_869_184
    assert costs.flash_flops_per_sequence(_cfg(12), T) == 3 * 12 * fwd_layer


@pytest.mark.parametrize('n_layer', [12, 24])
def test_decode_step_bytes(n_layer):
    weights = n_layer * (4 * D * D + 2 * D * F + 9 * D + F) + 2 * D \
        + D * V + V
    live = 5000
    kv = live * n_layer * 2 * D
    assert costs.decode_step_bytes(_cfg(n_layer), live) == 4 * (weights + kv)
    assert costs.kv_bytes_per_token(_cfg(24)) == 393_216


def test_peaks_known_and_unknown():
    p = peaks.peaks_of('TPU v5 lite')
    assert p['bf16_flops'] == 197e12 and p['hbm_bytes_s'] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks_of('TPU v9')


def test_config_files_hold_the_published_widths():
    for name, layers in (('train', 12), ('serve', 24)):
        c = manifest.read_json(
            'benchmarks/configs/cerebras-gpt-1.3b-%s.json' % name)
        assert (c['n_embd'], c['n_head'], c['n_inner'], c['n_positions'],
                c['vocab_size'], c['n_layer']) == (D, H, F, T, V, layers)
        changed = [k for k, v in c['published'].items() if c[k] != v]
        assert changed == c['reduced']
