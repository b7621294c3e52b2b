"""Model FLOPs per token of mamba-110m against a count by hand."""
from bench.lib.flops import (forward_flops_per_token, matmul_params,
                             train_flops_per_token)
from bench.lib.harness import load_json
from bench.lib.model import sizes


def test_mamba_110m_by_hand():
    s = sizes(load_json("configs", "mamba-110m"))
    # per layer: in_proj 1024x4096, x_proj 2048x(64+2*16), dt_proj 64x2048,
    # out_proj 2048x1024; 16 layers; head 1024x50280
    per_layer = 1024 * 4096 + 2048 * 96 + 64 * 2048 + 2048 * 1024
    assert per_layer == 6_619_136
    assert matmul_params(s) == 16 * per_layer + 1024 * 50280 == 157_392_896
    # scan 7*2048*16 + 3*2048, conv 2*4*2048, per layer
    elementwise = 16 * (7 * 2048 * 16 + 3 * 2048 + 2 * 4 * 2048)
    assert forward_flops_per_token(s) == 2 * 157_392_896 + elementwise
    assert train_flops_per_token(s) == 956_448_768


def test_training_flops_do_not_depend_on_execution_options():
    conf = load_json("configs", "mamba-1.4b-s12")
    s = sizes(conf)
    assert (s.d, s.di, s.N, s.R, s.vocab) == (2048, 4096, 16, 128, 50280)
    assert train_flops_per_token(s) == 3 * forward_flops_per_token(s)
