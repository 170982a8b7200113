"""HF weight import: logits parity with transformers' LlamaForCausalLM on a
tiny randomly-initialized model saved to disk (safetensors)."""
import numpy as np
import pytest

torch = pytest.importorskip("torch")
transformers = pytest.importorskip("transformers")


@pytest.fixture(scope="module")
def tiny_hf_dir(tmp_path_factory):
    from transformers import LlamaConfig, LlamaForCausalLM
    cfg = LlamaConfig(
        vocab_size=128, hidden_size=32, intermediate_size=96,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64, rms_norm_eps=1e-5, rope_theta=10000.0,
        tie_word_embeddings=False)
    torch.manual_seed(0)
    model = LlamaForCausalLM(cfg).eval()
    d = tmp_path_factory.mktemp("hf_llama")
    model.save_pretrained(str(d), safe_serialization=True)
    return d, model


def test_import_matches_hf_logits(tiny_hf_dir):
    d, hf_model = tiny_hf_dir
    from dla_tpu.models.hf_import import (
        hf_config_to_model_config,
        import_hf_weights,
        read_hf_config,
    )
    from dla_tpu.models.transformer import Transformer
    import jax.numpy as jnp

    hf_cfg = read_hf_config(d)
    cfg = hf_config_to_model_config(
        hf_cfg, dtype="float32", param_dtype="float32", remat="none")
    assert cfg.num_kv_heads == 2 and cfg.num_layers == 2
    params = import_hf_weights(d, cfg)
    model = Transformer(cfg)

    rs = np.random.RandomState(0)
    ids = rs.randint(0, 128, (2, 10))
    ours = np.asarray(model.apply(params, jnp.asarray(ids, jnp.int32)))
    with torch.no_grad():
        theirs = hf_model(torch.tensor(ids)).logits.numpy()
    np.testing.assert_allclose(ours, theirs, rtol=2e-3, atol=2e-4)


def test_load_causal_lm_resolves_hf_dir(tiny_hf_dir):
    d, _ = tiny_hf_dir
    import jax
    from dla_tpu.training.model_io import load_causal_lm
    bundle = load_causal_lm(
        str(d), {"tokenizer": "byte", "dtype": "float32",
                 "param_dtype": "float32", "remat": "none"},
        jax.random.key(0))
    assert bundle.config.vocab_size == 128
    assert bundle.params["layers"]["wq"].shape == (2, 32, 32)


def test_hf_config_sliding_window_mapping():
    """mistral's sliding_window maps through; qwen2-style configs that
    ship the key with use_sliding_window: false stay full-causal."""
    from dla_tpu.models.hf_import import hf_config_to_model_config

    base = dict(model_type="mistral", vocab_size=128, hidden_size=32,
                intermediate_size=64, num_hidden_layers=2,
                num_attention_heads=4, num_key_value_heads=2)
    assert hf_config_to_model_config(
        {**base, "sliding_window": 4096}).sliding_window == 4096
    assert hf_config_to_model_config(base).sliding_window is None
    assert hf_config_to_model_config(
        {**base, "model_type": "qwen2", "sliding_window": 131072,
         "use_sliding_window": False}).sliding_window is None
    assert hf_config_to_model_config(
        {**base, "sliding_window": None}).sliding_window is None


def test_hf_config_partial_sliding_window_rejected():
    """qwen2's max_window_layers: the FIRST mwl layers run full
    attention, SWA applies from layer mwl on (HF configuration_qwen2.py
    layer_types). Only mwl=0 (SWA everywhere) maps to the global window;
    mwl >= L disables SWA entirely; in between is per-layer — refused."""
    import pytest
    from dla_tpu.models.hf_import import hf_config_to_model_config

    cfg = dict(model_type="qwen2", vocab_size=128, hidden_size=32,
               intermediate_size=64, num_hidden_layers=28,
               num_attention_heads=4, num_key_value_heads=2,
               sliding_window=4096, use_sliding_window=True,
               max_window_layers=21)
    with pytest.raises(ValueError, match="max_window_layers"):
        hf_config_to_model_config(cfg)
    # mwl >= L: every layer full attention — window must NOT apply
    cfg["max_window_layers"] = 28
    assert hf_config_to_model_config(cfg).sliding_window is None
    # mwl == 0: SWA on every layer — exactly the global window
    cfg["max_window_layers"] = 0
    assert hf_config_to_model_config(cfg).sliding_window == 4096


def test_hub_snapshot_opt_in_and_fallback(tiny_hf_dir, monkeypatch):
    """DLA_HF_HUB_DOWNLOAD gates the hub path: off -> never called; on ->
    snapshot_download's directory imports through the local-dir path; a
    failing fetch falls back to preset init loudly instead of raising."""
    import jax

    from dla_tpu.training import model_io

    d, _ = tiny_hf_dir
    calls = []

    def fake_snapshot(repo_id, **kw):
        calls.append(repo_id)
        return str(d)

    import sys, types
    fake_mod = types.SimpleNamespace(snapshot_download=fake_snapshot)
    monkeypatch.setitem(sys.modules, "huggingface_hub", fake_mod)

    # flag off: hub never consulted, name falls through to the registry
    monkeypatch.delenv("DLA_HF_HUB_DOWNLOAD", raising=False)
    assert model_io._try_hub_snapshot("org/name") is None
    assert calls == []

    # flag on: the snapshot dir loads through the HF import path
    monkeypatch.setenv("DLA_HF_HUB_DOWNLOAD", "1")
    bundle = model_io.load_causal_lm(
        "org/tiny-llama", {"tokenizer": "byte"}, jax.random.key(0))
    assert calls == ["org/tiny-llama"]
    assert bundle.config.num_layers == 2  # the hf dir's architecture

    # failing fetch: loud fallback, no exception
    def broken(repo_id, **kw):
        raise OSError("no egress")
    fake_mod.snapshot_download = broken
    assert model_io._try_hub_snapshot("org/other") is None


def test_llama31_rope_scaling_logits_parity(tmp_path):
    """llama3-type rope_scaling (llama-3.1/3.2): imported weights +
    scaled frequencies must reproduce transformers' logits, with
    positions past original_max_position_embeddings in play."""
    import jax.numpy as jnp
    from transformers import LlamaConfig, LlamaForCausalLM

    from dla_tpu.models.hf_import import (
        hf_config_to_model_config,
        import_hf_weights,
        read_hf_config,
    )
    from dla_tpu.models.transformer import Transformer

    cfg = LlamaConfig(
        vocab_size=128, hidden_size=32, intermediate_size=96,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=64, rms_norm_eps=1e-5, rope_theta=10000.0,
        tie_word_embeddings=False,
        rope_scaling={"rope_type": "llama3", "factor": 4.0,
                      "low_freq_factor": 1.0, "high_freq_factor": 4.0,
                      "original_max_position_embeddings": 16})
    torch.manual_seed(1)
    hf_model = LlamaForCausalLM(cfg).eval()
    d = tmp_path / "hf31"
    hf_model.save_pretrained(str(d), safe_serialization=True)

    mc = hf_config_to_model_config(
        read_hf_config(d), dtype="float32", param_dtype="float32",
        remat="none")
    assert mc.rope_scaling and mc.rope_scaling["factor"] == 4.0
    params = import_hf_weights(d, mc)
    model = Transformer(mc)

    rs = np.random.RandomState(0)
    ids = rs.randint(0, 128, (2, 40))  # well past the original 16 ctx
    ours = np.asarray(model.apply(params, jnp.asarray(ids, jnp.int32)))
    with torch.no_grad():
        theirs = hf_model(torch.tensor(ids)).logits.numpy()
    np.testing.assert_allclose(ours, theirs, rtol=2e-3, atol=2e-4)


def test_dynamic_ntk_rope_matches_hf():
    """Dynamic NTK rope scaling: traced base stretch past the trained
    context, unit parity with ROPE_INIT_FUNCTIONS['dynamic'] on both
    sides, end-to-end logits parity on a tiny llama run BEYOND its
    max_position_embeddings."""
    import jax.numpy as jnp
    import torch
    from transformers import LlamaConfig, LlamaForCausalLM
    from transformers.modeling_rope_utils import ROPE_INIT_FUNCTIONS

    from dla_tpu.models.hf_import import (
        _validated_rope_scaling,
        hf_config_to_model_config,
        import_hf_weights,
        read_hf_config,
    )
    from dla_tpu.ops.rotary import _dynamic_ntk_inv_freq

    hd, theta, max_pos = 16, 10000.0, 32
    hf_cfg = LlamaConfig(
        vocab_size=160, hidden_size=hd * 4, intermediate_size=96,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        max_position_embeddings=max_pos, rope_theta=theta,
        tie_word_embeddings=False,
        rope_scaling={"rope_type": "dynamic", "factor": 4.0})
    scaling = _validated_rope_scaling(hf_cfg.to_dict())
    assert scaling["max_position_embeddings"] == max_pos
    for seq_len in (max_pos - 8, max_pos * 3):
        inv_hf, _ = ROPE_INIT_FUNCTIONS["dynamic"](
            hf_cfg, device="cpu", seq_len=seq_len)
        inv_j = _dynamic_ntk_inv_freq(
            scaling, jnp.arange(seq_len)[None, :], hd, theta)
        np.testing.assert_allclose(np.asarray(inv_j), inv_hf.numpy(),
                                   rtol=1e-6, err_msg=f"seq={seq_len}")

    import tempfile
    torch.manual_seed(5)
    hf_model = LlamaForCausalLM(hf_cfg).eval()
    with tempfile.TemporaryDirectory() as d:
        hf_model.save_pretrained(d, safe_serialization=True)
        cfg = hf_config_to_model_config(
            read_hf_config(d), dtype="float32", param_dtype="float32",
            remat="none", max_seq_length=96)
        params = import_hf_weights(d, cfg)
    from dla_tpu.models.transformer import Transformer
    model = Transformer(cfg)
    for t in (max_pos - 8, max_pos + 16):  # static base, stretched base
        ids = np.random.RandomState(6).randint(0, 160, (2, t))
        ours = np.asarray(model.apply(params, jnp.asarray(ids, np.int32)))
        with torch.no_grad():
            theirs = hf_model(torch.tensor(ids)).logits.numpy()
        np.testing.assert_allclose(ours, theirs, rtol=2e-3, atol=3e-4,
                                   err_msg=f"T={t}")


def test_unknown_rope_scaling_refused():
    import pytest
    from dla_tpu.models.hf_import import hf_config_to_model_config

    base = dict(model_type="llama", vocab_size=128, hidden_size=32,
                intermediate_size=64, num_hidden_layers=2,
                num_attention_heads=4, num_key_value_heads=2)
    with pytest.raises(NotImplementedError, match="made_up"):
        hf_config_to_model_config(
            {**base,
             "rope_scaling": {"rope_type": "made_up", "factor": 2.0}})
    # default-type scaling dicts are a no-op, not an error
    assert hf_config_to_model_config(
        {**base, "rope_scaling": {"rope_type": "default"}}
    ).rope_scaling is None


def test_arch_overrides_cover_every_model_config_field():
    """model.<key> YAML overrides flow to ModelConfig through a
    whitelist in model_io._arch_overrides — a field missing from it is
    SILENTLY dropped (round 4: --set model.pipeline_interleave=2 was a
    no-op). Pin that every architecture-shaping ModelConfig field is
    either whitelisted or deliberately excluded."""
    import dataclasses

    from dla_tpu.models.config import ModelConfig
    from dla_tpu.training.model_io import _arch_overrides

    # fields set by structural/weight context, not per-run YAML keys
    excluded = {
        "vocab_size", "hidden_size", "intermediate_size",
        "num_heads", "num_kv_heads", "head_dim", "rope_theta",
        "rope_scaling", "rms_norm_eps", "tie_embeddings",
        "max_seq_length",  # handled explicitly above the whitelist
        "flash_block_q", "flash_block_k",
        "lora_r", "lora_alpha", "lora_dropout", "lora_targets",  # lora block
        # the per-layer spec and what it implies: the architecture's own
        # shape, read from the model's config like the widths above
        "layers", "norm", "ssm_state_size", "ssm_conv_width", "ssm_expand",
        "ssm_dt_rank", "ssm_inner_norms",
    }
    fields = {f.name for f in dataclasses.fields(ModelConfig)}
    candidates = fields - excluded
    probe = {k: 1 for k in candidates}
    got = _arch_overrides(probe)
    missing = candidates - set(got)
    assert not missing, (
        f"ModelConfig fields silently dropped by _arch_overrides: "
        f"{sorted(missing)}")
