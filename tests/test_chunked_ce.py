"""Sequence-chunked cross-entropy parity (llama.ce_chunk): the 32k-context
loss path must produce the same loss/grads as the whole-sequence CE.
Anchor: SURVEY §5.7 long-context scale."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeflow_tpu.models import llama


def _cfgs(**kw):
    base = dict(vocab_size=128, d_model=32, n_layers=2, n_heads=4,
                n_kv_heads=2, d_ff=64, max_seq_len=64,
                attention_impl="xla", dtype=jnp.float32, remat=False, **kw)
    return (llama.LlamaConfig(**base),
            llama.LlamaConfig(**base, ce_chunk=16))


def test_chunked_ce_matches_plain_loss_and_grads():
    plain_cfg, chunked_cfg = _cfgs()
    params = llama.init(jax.random.key(0), plain_cfg)
    tokens = jax.random.randint(jax.random.key(1), (2, 64), 0, 128,
                                jnp.int32)
    batch = {"tokens": tokens}
    (l0, aux0), g0 = jax.value_and_grad(llama.loss_fn, has_aux=True)(
        params, batch, plain_cfg)
    (l1, aux1), g1 = jax.value_and_grad(llama.loss_fn, has_aux=True)(
        params, batch, chunked_cfg)
    np.testing.assert_allclose(float(l0), float(l1), rtol=1e-5)
    assert float(aux0["tokens"]) == float(aux1["tokens"])
    for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(g1)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-6)


def test_chunked_ce_respects_loss_mask():
    plain_cfg, chunked_cfg = _cfgs()
    params = llama.init(jax.random.key(0), plain_cfg)
    tokens = jax.random.randint(jax.random.key(1), (2, 64), 0, 128,
                                jnp.int32)
    mask = (jax.random.uniform(jax.random.key(2), (2, 64)) < 0.7
            ).astype(jnp.float32)
    batch = {"tokens": tokens, "loss_mask": mask}
    l0, _ = llama.loss_fn(params, batch, plain_cfg)
    l1, _ = llama.loss_fn(params, batch, chunked_cfg)
    np.testing.assert_allclose(float(l0), float(l1), rtol=1e-5)


def test_chunked_ce_rejects_nondividing_chunk():
    _, chunked_cfg = _cfgs()
    params = llama.init(jax.random.key(0), chunked_cfg)
    tokens = jnp.zeros((1, 40), jnp.int32)   # 40 % 16 != 0
    with pytest.raises(ValueError, match="ce_chunk"):
        llama.loss_fn(params, {"tokens": tokens}, chunked_cfg)
