"""Phi-3 through the dense family against the plain float32 reference
(`chipbench/reference/dense_lm.py`) on seeded random weights, at a tiny
Phi-3 shape: untied embeddings, RMSNorm eps 1e-5, head_dim set, a
sliding window on every layer."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
sys.path.insert(0, REPO)

from chipbench.drivers.lm_round import arch_config  # noqa: E402
from chipbench.reference import dense_lm as ref  # noqa: E402
from repro.configs import gemma2_9b, llama32_3b, phi3_mini_38b  # noqa: E402
from repro.models import api, dense  # noqa: E402
from repro.models import layers as L  # noqa: E402

TINY = dict(name="phi3-tiny", hidden_size=64, intermediate_size=128,
            num_attention_heads=4, num_key_value_heads=4, head_dim=16,
            num_hidden_layers=2, vocab_size=256, rope_theta=10000.0,
            rms_norm_eps=1e-5, sliding_window=47, tie_word_embeddings=False,
            dtype="float32")
S = 32      # shorter than the window, as the cell's 1024 is under 2047


def weights(cfg, seed=0):
    """The reference's init, with the norm weights drawn too (they are
    zeros at init, and the norms' eps should meet real weights)."""
    p = ref.init(cfg, jax.random.PRNGKey(seed))
    k = jax.random.PRNGKey(seed + 1)
    return jax.tree_util.tree_map_with_path(
        lambda path, x: 0.1 * jax.random.normal(k, x.shape, x.dtype)
        if "norm" in jax.tree_util.keystr(path) else x, p)


def batch(cfg, seed=2, b=2, s=S):
    t = jax.random.randint(jax.random.PRNGKey(seed), (b, s + 1), 0,
                           cfg["vocab_size"])
    return dict(tokens=t[:, :-1], labels=t[:, 1:])


def test_preset_states_the_published_eps():
    assert phi3_mini_38b.CONFIG.norm_eps == 1e-5
    assert llama32_3b.CONFIG.norm_eps == 1e-6


@pytest.mark.parametrize("eps", [1e-5, 1e-6])
def test_forward_and_loss_match_reference(eps):
    cfg = dict(TINY, rms_norm_eps=eps)
    arch = arch_config(cfg)
    assert dense.member_kind(arch, 0) == "local"
    p, b = weights(cfg), batch(cfg)
    with jax.default_matmul_precision("highest"):
        got = dense.forward(arch, p, b["tokens"])
        want = ref.logits(cfg, p, b["tokens"])
        loss, ref_loss = api.loss(arch, p, b), ref.loss(cfg, p, b)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(loss, ref_loss, rtol=1e-6)


def test_eps_changes_the_result():
    """1e-5 against 1e-6 moves the logits by far more than the program's
    distance to the reference: the option reaches every norm."""
    p, b = weights(TINY), batch(TINY)
    with jax.default_matmul_precision("highest"):
        five = dense.forward(arch_config(TINY), p, b["tokens"])
        six = dense.forward(arch_config(dict(TINY, rms_norm_eps=1e-6)), p,
                            b["tokens"])
        ref_five = ref.logits(TINY, p, b["tokens"])
    gap = float(jnp.max(jnp.abs(five - six)))
    assert gap > 100 * float(jnp.max(jnp.abs(five - ref_five))), gap


@pytest.mark.parametrize("preset", [llama32_3b, gemma2_9b],
                         ids=["llama32", "gemma2"])
def test_default_eps_is_the_old_fixed_norm_bit_for_bit(preset, monkeypatch):
    """A config that states no eps computes what the norm with its eps
    fixed at 1e-6 computed, bit for bit (forward and decode)."""
    cfg = preset.CONFIG.reduced().replace(dtype="float32")
    p = api.init_params(cfg, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab)
    cache = dense.init_cache(cfg, 2, 16)
    new = (dense.forward(cfg, p, toks),
           dense.decode_step(cfg, p, cache, toks[:, :1], jnp.int32(0))[0])
    fixed = L.rmsnorm
    monkeypatch.setattr(L, "rmsnorm", lambda x, w, eps=None: fixed(x, w))
    old = (dense.forward(cfg, p, toks),
           dense.decode_step(cfg, p, cache, toks[:, :1], jnp.int32(0))[0])
    for a, b in zip(new, old):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_window_masks_every_layer_of_a_uniform_model():
    """The window moves nothing while the sequence is shorter than it,
    and masks when the sequence is longer: every layer of a uniform model
    with a window is windowed."""
    cfg = dict(TINY, sliding_window=8)
    p, b = weights(cfg), batch(cfg)
    none = arch_config(dict(cfg, sliding_window=None))
    assert dense.member_kind(none, 0) == "full"
    with jax.default_matmul_precision("highest"):
        full = dense.forward(none, p, b["tokens"])
        wide = dense.forward(arch_config(dict(cfg, sliding_window=S)), p,
                             b["tokens"])
        narrow = dense.forward(arch_config(cfg), p, b["tokens"])
    assert np.array_equal(np.asarray(full), np.asarray(wide))
    # the first 8 positions see the same keys either way
    np.testing.assert_allclose(narrow[:, :8], full[:, :8], rtol=1e-5,
                               atol=1e-5)
    assert float(jnp.max(jnp.abs(narrow[:, 8:] - full[:, 8:]))) > 1e-3
