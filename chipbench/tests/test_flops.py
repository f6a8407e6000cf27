"""The FLOP and byte functions against hand-worked numbers."""
import json
import os

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))


def cfg(name):
    return json.load(open(os.path.join(HERE, "..", "configs", name + ".json")))


def test_train_flops_per_update():
    from flops import gpt2
    m, l = cfg("gpt2-medium"), cfg("gpt2-large")
    # per layer 8e^2 + 4ef + 2se, head 2eV, x3 for forward + backward
    fwd_m = 24 * (8 * 1024 ** 2 + 4 * 1024 * 4096 + 2 * 1024 * 1024) \
        + 2 * 1024 * 50257
    assert gpt2.forward_flops_per_token(m, 1024) == fwd_m
    assert gpt2.train_flops_per_token(m, 1024) * 8 * 1024 \
        == pytest.approx(18.61e12, rel=1e-3)
    assert gpt2.train_flops_per_token(l, 1024) * 32 * 1024 \
        == pytest.approx(161.08e12, rel=1e-3)


def test_flash_needed_work():
    from flops import gpt2
    m = cfg("gpt2-medium")
    # six products of 2*s*s*d, halved, per head; 16 heads x 24 layers x 8
    per_head = 6 * 2 * 1024 * 1024 * 64 // 2
    assert gpt2.flash_train_flops(m, 8, 1024) == 24 * 8 * 16 * per_head
    assert gpt2.flash_train_bytes(m, 8, 1024) \
        == 24 * 8 * 16 * 12 * 1024 * 64 * 2
    # attention's core is 2se of the 8e^2 + 4ef + 2se a layer needs
    share = gpt2.flash_train_flops(m, 8, 1024) \
        / (gpt2.train_flops_per_token(m, 1024) * 8 * 1024)
    assert 0.06 < share < 0.08


def test_parameter_counts():
    from families import gpt2
    for name in ("gpt2-medium", "gpt2-large"):
        c = cfg(name)
        assert gpt2.n_params(c) == c["parameters"]
