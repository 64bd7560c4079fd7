"""The required-operations functions against hand counts."""
import json
import os

from conftest import CHIP


def _cfg(name):
    with open(os.path.join(CHIP, 'configs', name + '.json')) as f:
        return json.load(f)


def test_resnet50_forward_is_the_papers_3_8_giga_multiply_adds():
    from models import resnet50
    cfg = _cfg('resnet50')
    macs = resnet50.forward_macs_per_image(cfg)
    # He et al., Table 1: 3.8e9 multiply-adds, with the stride on a
    # bottleneck's first 1x1 as paddle_tpu/models/resnet.py has it. (The
    # 4.09e9 often quoted is the later placement on the 3x3.)
    assert 3.8e9 < macs < 3.9e9
    step = resnet50.required_flops(cfg, {'batch': 256})
    assert step == 6 * macs * 256
    # a hand count of the stem and the classifier
    stem = 64 * 3 * 49 * 112 * 112
    assert stem == 118013952
    assert resnet50._conv_specs(cfg)[0] == (64, 3, 7, 2, 3)
    assert len(resnet50._conv_specs(cfg)) == 53


def transformer_flops_per_token(n_layers, d_model, vocab, seq):
    """Copy of paddle_tpu/observability/perf.py's arithmetic (the
    yardstick keeps its own copy): 6 per matmul weight, attention dots
    at 12 * layers * (S/2) * d."""
    n_matmul = n_layers * 12 * d_model * d_model + vocab * d_model
    return 6 * n_matmul + 12 * n_layers * (seq // 2) * d_model


def test_opt_matches_the_transformer_arithmetic():
    from models import opt
    cfg = _cfg('opt-1.3b')
    traffic = {'batch': 2, 'seq_len': 2048}
    assert cfg['ffn_dim'] == 4 * cfg['hidden_size']
    per_token = opt.required_flops(cfg, traffic) / (2 * 2048)
    want = transformer_flops_per_token(
        cfg['num_hidden_layers'], cfg['hidden_size'], cfg['vocab_size'],
        2048)
    assert per_token == want
    assert 3.0e9 < per_token < 3.6e9
    # flash: forward two matmuls under the causal mask, backward four
    f, b = opt.flash_fwd_work(cfg, traffic, 1)
    L, H = cfg['num_hidden_layers'], cfg['hidden_size']
    # B rows x (QK^T and PV: 2 * 2*S*S*H operations) / 2 for the mask
    assert f == L * 2 * (2 * 2 * 2048 * 2048 * H) // 2
    assert opt.flash_bwd_work(cfg, traffic, 1)[0] == 2 * f
    assert b == L * 4 * 2 * 2048 * H * 2
