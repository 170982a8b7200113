"""Cross-lower the Pallas kernels for TPU from the CPU, uninterpreted, at
Mistral-7B head shapes (32q/8kv x 128, T = 2048).

Interpret mode runs a kernel body as plain XLA ops and never meets
Mosaic's rules on block shapes and vector ops, so the parity suites
cannot see a kernel that does not lower — the decode kernel shipped that
way (``(1, n)`` blocks over ``[B, n]`` arrays, a one-column bf16 dot).
Lowering to a ``tpu_custom_call`` is the part of the chip's compiler a
CPU can run; whether Mosaic then compiles it is chip_smoke.py's job."""
import jax
import jax.numpy as jnp
import pytest

from dla_tpu.ops.decode_kernel import flash_decode_attention
from dla_tpu.ops.flash_attention import flash_causal_attention
from dla_tpu.ops.quant_matmul import int8_matmul

H, KH, D, T = 32, 8, 128, 2048


def _tpu_text(fn, *args) -> str:
    return jax.jit(fn).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text()


def _sds(shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype)


@pytest.mark.parametrize("block", [512, 1024])
@pytest.mark.parametrize("window", [None, 4096])
def test_flash_attention_forward_and_backward_lower_for_tpu(block, window):
    q, kv = _sds((2, T, H, D)), _sds((2, T, KH, D))

    def loss(q, k, v):
        out = flash_causal_attention(q, k, v, block_q=block, block_k=block,
                                     window=window, interpret=False)
        return out.astype(jnp.float32).sum()

    text = _tpu_text(jax.grad(loss, argnums=(0, 1, 2)), q, kv, kv)
    # forward, dQ and dK/dV kernels
    assert text.count("tpu_custom_call") >= 3


@pytest.mark.parametrize("m", [8, 2048])
@pytest.mark.parametrize("k,n", [(4096, 4096), (4096, 14336),
                                 (14336, 4096), (4096, 1024),
                                 (4096, 32000)])
def test_int8_matmul_lowers_for_tpu(m, k, n):
    text = _tpu_text(
        lambda x, w, s: int8_matmul(x, w, s, interpret=False),
        _sds((m, k)), _sds((k, n), jnp.int8), _sds((1, n), jnp.float32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("batch", [1, 8, 64])
@pytest.mark.parametrize("cache_dtype", [jnp.bfloat16, jnp.int8])
def test_decode_attention_lowers_for_tpu(batch, cache_dtype):
    s = 2048
    q, new = _sds((batch, 1, H, D)), _sds((batch, 1, KH, D))
    cache = _sds((batch, s, KH, D), cache_dtype)
    bias = _sds((batch, s), jnp.float32)
    fill = _sds((), jnp.int32)
    scales = {}
    if cache_dtype == jnp.int8:
        scales = dict(k_scale=_sds((batch, KH, s), jnp.float32),
                      v_scale=_sds((batch, KH, s), jnp.float32))

    def attend(q, kc, vc, kn, vn, bias, fill, scales):
        return flash_decode_attention(q, kc, vc, kn, vn, bias=bias,
                                      kv_fill=fill, interpret=False,
                                      **scales)

    text = _tpu_text(attend, q, cache, cache, new, new, bias, fill, scales)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("slots", [16, 64])
@pytest.mark.parametrize("h,kh,d,softcap", [
    (32, 8, 128, 0.0),      # mistral-7b: the dense serving cells
    (16, 8, 256, 50.0),     # gemma-2 9b: two 128-lane copies a page
    (16, 16, 256, 0.0),     # gemma 7b: no grouping
    (32, 4, 128, 0.0),      # a group of 8
], ids=["mistral", "gemma2", "gemma", "group8"])
def test_paged_decode_attention_lowers_for_tpu(slots, h, kh, d, softcap):
    """The paged kernel at the serving cells' geometry: the pools whole
    ([layers, pages, 16, K, D], as stored), 2,048-token windows."""
    from dla_tpu.ops.paged_attention import paged_decode_attention
    pool = _sds((4, 128 * slots // 8, 16, kh, d))

    def attend(q, kp, vp, tables, lengths, kn, vn, layer, window):
        return paged_decode_attention(
            q, kp, vp, tables, lengths, kn, vn, layer=layer, window=window,
            logit_softcap=softcap, interpret=False)

    text = _tpu_text(
        attend, _sds((slots, h, d)), pool, pool,
        _sds((slots, 128), jnp.int32), _sds((slots,), jnp.int32),
        _sds((slots, kh, d)), _sds((slots, kh, d)), _sds((), jnp.int32),
        _sds((), jnp.int32))
    assert text.count("tpu_custom_call") == 1


def test_sharded_train_forward_keeps_the_flash_kernel_on_tpu(
        mesh8, monkeypatch):
    """Under a multi-device mesh the model wraps the flash call in a
    shard_map; Mosaic refuses a kernel unless EVERY mesh axis is manual
    there, size-1 axes included — a lowering-time error the interpreted
    kernel never raises."""
    import functools

    from dla_tpu.models.config import ModelConfig
    from dla_tpu.models.transformer import Transformer
    from dla_tpu.ops import flash_attention

    monkeypatch.setattr(
        flash_attention, "flash_causal_attention",
        functools.partial(flash_attention.flash_causal_attention,
                          interpret=False))
    model = Transformer(ModelConfig(
        vocab_size=512, hidden_size=512, intermediate_size=1024,
        num_layers=2, num_heads=4, num_kv_heads=2, max_seq_length=256,
        attention="flash", remat="full"))
    params = jax.eval_shape(model.init, jax.random.key(0))
    ids = _sds((4, 256), jnp.int32)

    def loss(params, ids):
        return model.apply(params, ids).astype(jnp.float32).sum()

    with jax.sharding.set_mesh(mesh8):
        text = _tpu_text(jax.grad(loss), params, ids)
    assert text.count("tpu_custom_call") >= 3
