"""The plain float32 reference against the program's ``Transformer`` at the
``tiny-gqa`` preset with a sliding window: full-sequence logits, a packed
row's loss (segment mask, restarted positions), and the weights handed
over a layer at a time."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench.lib import sut
from perfbench.lib.manifest import Manifest
from perfbench.tests.conftest import ROOT


@pytest.fixture(scope="module")
def setup():
    from dla_tpu.models.config import get_model_config
    from dla_tpu.models.transformer import Transformer
    mc = dataclasses.replace(get_model_config("tiny-gqa"), sliding_window=24)
    model = Transformer(mc)
    params = sut.init_params(model, 2 ** 31 + 5)
    cfg = {"num_hidden_layers": mc.num_layers, "hidden_size": mc.hidden_size,
           "intermediate_size": mc.intermediate_size,
           "num_attention_heads": mc.num_heads,
           "num_key_value_heads": mc.num_kv_heads,
           "vocab_size": mc.vocab_size, "sliding_window": 24,
           "rms_norm_eps": mc.rms_norm_eps, "rope_theta": mc.rope_theta}
    ref = Manifest(ROOT).reference("mistral_block")
    return model, params, cfg, ref


def test_model_config_maps_the_hugging_face_keys():
    cfg = Manifest(ROOT).config("mistral7b_serve_d16")
    mc = sut.model_config(cfg, dtype="bfloat16", param_dtype="bfloat16",
                          attention="flash", max_seq_length=2048)
    assert (mc.hidden_size, mc.intermediate_size, mc.num_layers) == (
        4096, 14336, 16)
    assert (mc.num_heads, mc.num_kv_heads, mc.head_dim_) == (32, 8, 128)
    assert mc.sliding_window == 4096 and mc.vocab_size == 32000
    assert not mc.tie_embeddings and mc.rope_theta == 10000.0


def test_logits_agree_beyond_the_window(setup):
    model, params, cfg, ref = setup
    ids = np.asarray(jax.random.randint(jax.random.key(1), (80,), 3, 500))
    want = model.apply(params, jnp.asarray(ids)[None])[0]
    embedding, layer, final_norm, lm_head = sut.reference_weights(params)
    hidden = ref.hidden_states(ids, embedding, layer, final_norm, cfg)
    got = ref.logits(hidden, lm_head)
    # float32 on both sides: only the order of summation differs
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=0, atol=2e-4)
    # the window is live: without it the last rows differ
    no_window = ref.hidden_states(ids, embedding, layer, final_norm,
                                  {**cfg, "sliding_window": None})
    assert float(jnp.abs(no_window[-1] - hidden[-1]).max()) > 1e-3
    assert float(jnp.abs(no_window[10] - hidden[10]).max()) < 1e-5


def test_packed_row_loss_agrees(setup):
    from dla_tpu.ops.fused_ce import model_fused_ce
    model, params, cfg, ref = setup
    t = 96
    rng = np.random.default_rng(3)
    ids = rng.integers(3, 500, t).astype(np.int32)
    segments = np.asarray([1] * 40 + [2] * 30 + [3] * 20 + [0] * 6, np.int32)
    labels = ids.copy()
    labels[segments == 0] = -100
    for start in (0, 40, 70):
        labels[start:start + 5] = -100        # prompt part and the seam
    batch = {"input_ids": jnp.asarray(ids)[None],
             "attention_mask": jnp.asarray(segments > 0, jnp.int32)[None],
             "labels": jnp.asarray(labels)[None],
             "segment_ids": jnp.asarray(segments)[None]}
    want, n = model_fused_ce(model, params, batch)
    embedding, layer, final_norm, lm_head = sut.reference_weights(params)
    hidden = ref.hidden_states(ids, embedding, layer, final_norm, cfg,
                               segments=segments)
    nll, count = ref.next_token_nll(hidden, lm_head, labels)
    assert int(count) == int(n)
    assert float(nll) / int(count) == pytest.approx(float(want), abs=2e-5)
    # documents are walled off: changing document 1 leaves document 2's
    # hidden states alone
    ids2 = ids.copy()
    ids2[:40] = rng.integers(3, 500, 40)
    other = ref.hidden_states(ids2, embedding, layer, final_norm, cfg,
                              segments=segments)
    assert float(jnp.abs(other[40:70] - hidden[40:70]).max()) < 1e-6


def test_a_lower_precision_would_fail_the_tolerance(setup):
    """The serving tolerance has to catch a path that computes in fewer
    bits than the configuration states: rounding the weights to 8 bits
    moves the logits of this tiny model by more than bf16 rounding does."""
    model, params, cfg, ref = setup
    ids = np.asarray(jax.random.randint(jax.random.key(2), (64,), 3, 500))
    embedding, layer, final_norm, lm_head = sut.reference_weights(params)
    exact = ref.logits(ref.hidden_states(
        ids, embedding, layer, final_norm, cfg), lm_head)

    def rounded(bits):
        def q(w):
            scale = jnp.max(jnp.abs(w)) / (2 ** (bits - 1) - 1)
            return jnp.round(w / scale) * scale
        return lambda l: {k: q(v) if v.ndim == 2 else v
                          for k, v in layer(l).items()}
    err8 = float(jnp.abs(ref.logits(ref.hidden_states(
        ids, embedding, rounded(8), final_norm, cfg), lm_head) - exact).max())
    bf16 = lambda l: {k: v.astype(jnp.bfloat16)  # noqa: E731
                      for k, v in layer(l).items()}
    err16 = float(jnp.abs(ref.logits(ref.hidden_states(
        ids, embedding, bf16, final_norm, cfg), lm_head) - exact).max())
    assert err8 > 3 * err16 > 0
