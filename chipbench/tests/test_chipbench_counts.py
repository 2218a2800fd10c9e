"""Operation, byte and parameter counts against hand-worked shapes."""
import math

import jax

from chipbench import harness
from chipbench.counts import dense_lm, lenet5, rloo_combine
from chipbench.reference import dense_lm as ref_lm, lenet5 as ref_lenet


def cfg(name):
    return harness.load_json(harness.HERE, "configs", name + ".json")


def test_lenet5_macs_by_hand():
    # conv1 28*28*6 outputs of 5*5*3; conv2 10*10*16 of 5*5*6; then
    # 400x120, 120x84, 84x10
    macs = 28 * 28 * 6 * 75 + 10 * 10 * 16 * 150 + 400 * 120 + 120 * 84 \
        + 84 * 10
    assert macs == 651_720
    assert lenet5.macs_per_sample(cfg("paper-lenet5")) == macs
    assert lenet5.train_flops_per_sample(cfg("paper-lenet5")) == 6 * macs


def test_lenet5_params_match_reference_and_program():
    from repro.models import lenet
    c = cfg("paper-lenet5")
    ours = jax.eval_shape(lambda: ref_lenet.init(c, jax.random.key(0)))
    prog = jax.eval_shape(lambda: lenet.init(lenet.LeNetConfig(),
                                             jax.random.key(0)))
    assert jax.tree.map(lambda x: x.shape, ours) == \
        jax.tree.map(lambda x: x.shape, prog)
    n = sum(math.prod(x.shape) for x in jax.tree.leaves(ours))
    # 5*5*3*6+6, 5*5*6*16+16, 400*120+120, 120*84+84, 84*10+10
    assert n == 456 + 2416 + 48_120 + 10_164 + 850 == 62_006


def test_phi3_4l_params_by_hand():
    c = cfg("phi3-mini-4l")
    d, f, v = 3072, 8192, 32064
    layer = 4 * d * d + 3 * d * f
    assert dense_lm.matmul_params(c) == 4 * layer + d * v
    assert dense_lm.n_params(c) == 4 * (layer + 2 * d) + 2 * d * v + d \
        == 650_013_696


def test_phi3_4l_tree_matches_program():
    from chipbench.drivers.make_round import arch_config
    from repro.models import api
    c = cfg("phi3-mini-4l")
    ours = jax.eval_shape(lambda: ref_lm.init(c, jax.random.key(0)))
    prog = jax.eval_shape(lambda: api.init_params(arch_config(c),
                                                  jax.random.key(0)))
    assert jax.tree.map(lambda x: (x.shape, x.dtype), ours) == \
        jax.tree.map(lambda x: (x.shape, x.dtype), prog)


def test_phi3_flops_per_token():
    c = cfg("phi3-mini-4l")
    attn = 3 * 4 * 4 * 32 * 96 * (1024 + 1) / 2
    assert dense_lm.train_flops_per_token(c, 1024) == \
        6 * dense_lm.matmul_params(c) + attn


def test_rloo_combine_bytes_with_and_without_tile():
    # K=2 rows of N=10 read, one row written; the tile adds K rows
    assert rloo_combine.bytes_needed(2, 10, tile_consumed=False) == 120
    assert rloo_combine.bytes_needed(2, 10, tile_consumed=True) == 200
    assert rloo_combine.flops(2, 10) == 150
