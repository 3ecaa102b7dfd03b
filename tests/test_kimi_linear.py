"""models/kimi_linear.py against the plain reference
(benchmark/reference/kimi_linear.py, which imports nothing of the program):
a 5-layer model of the benchmark cut's layer kinds (KDA + dense FFN; KDA, KDA,
latent attention, KDA with routed and shared experts), toy widths, seeded
random weights."""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark"))

from reference import kimi_linear as ref  # noqa: E402

from kubeflow_tpu.models import kimi_linear as kl, registry  # noqa: E402
from kubeflow_tpu.ops import flash_pallas  # noqa: E402
from kubeflow_tpu.ops.attention import mha  # noqa: E402
from kubeflow_tpu.ops.flash_attention import flash_attention  # noqa: E402

CFG = dataclasses.replace(kl.KimiLinearConfig.tiny(), dtype=jnp.float32,
                          attention_impl="xla")
#: the same model as the reference reads it: the published config's keys
HF = dict(hidden_size=64, vocab_size=512, num_hidden_layers=5,
          rms_norm_eps=1e-5, linear_attn_config=CFG.linear_attn_config,
          num_attention_heads=2, qk_nope_head_dim=16, qk_rope_head_dim=8,
          v_head_dim=16, kv_lora_rank=32, intermediate_size=128,
          moe_intermediate_size=32, first_k_dense_replace=1, num_experts=4,
          published={"num_experts": 8}, first_expert=0,
          num_experts_per_token=2, routed_scaling_factor=2.446,
          moe_renormalize=True)


def flat(tree):
    return {"/".join(str(k.key) for k in path): leaf for path, leaf in
            jax.tree_util.tree_flatten_with_path(tree)[0]}


@pytest.fixture(scope="module")
def params():
    return kl.init(jax.random.key(3), CFG)


@pytest.fixture(scope="module")
def tokens():
    return jax.random.randint(jax.random.key(1), (2, 96), 0, 512)


def test_the_registry_lists_the_family():
    assert "kimi_linear" in registry.names()
    assert registry.get("kimi_linear").config_cls is kl.KimiLinearConfig


def test_init_is_the_references_draw_bit_for_bit(params):
    mine, theirs = flat(params), flat(ref.init_params(3, HF))
    assert mine.keys() == {ref.leaf_of(k) for k in theirs}
    for name, leaf in mine.items():
        assert bool(jnp.array_equal(leaf, theirs[name])), name


def test_the_layer_kinds_are_the_cuts(params):
    kinds = [("kda" if CFG.is_kda(i) else "latent",
              "dense" if CFG.is_dense(i) else "experts") for i in range(5)]
    assert kinds == [("kda", "dense"), ("kda", "experts"),
                     ("kda", "experts"), ("latent", "experts"),
                     ("kda", "experts")]
    assert "wkva" in params["layers"]["03"] and "A_log" in params["layers"]["04"]
    with pytest.raises(ValueError):
        dataclasses.replace(CFG, linear_attn_config=dict(
            CFG.linear_attn_config, kda_layers=[1, 2]))


# float32 compute at the highest matmul precision on both sides: the
# program's chunked rule, sorted routing and chunked loss sum in another
# order than the reference's position-by-position loops, nothing else
# differs. Logits to 1e-4 of the largest (measured 4e-6), the loss to 1e-5
# (measured equal), every leaf's gradient to 1e-3 of that leaf's largest
# entry (measured 1e-5); a bfloat16 matmul anywhere reads 1e-2.
def test_logits_loss_and_every_leafs_gradient_match_the_reference(params,
                                                                  tokens):
    with jax.default_matmul_precision("highest"):
        logits = kl.apply(params, tokens, CFG)
        want = ref.logits(params, tokens, HF)
        assert float(jnp.max(jnp.abs(logits - want))) < 1e-4 * float(
            jnp.max(jnp.abs(want)))
        (loss, metrics), grads = jax.value_and_grad(
            lambda p: kl.loss_fn(p, {"tokens": tokens}, CFG),
            has_aux=True)(params)

        def ref_loss(p):
            total, count = ref.loss_sum(p, tokens, HF)
            return total / count

        want_loss, want_grads = jax.value_and_grad(ref_loss)(params)
    assert abs(float(loss) - float(want_loss)) < 1e-5 * float(want_loss)
    assert float(metrics["moe_rows_dropped"]) == 0
    assert float(metrics["moe_rows_here"]) > 0
    assert float(metrics["moe_expert_load_max_over_mean"]) >= 1.0
    assert 0 < float(metrics["router_top1_share_max"]) <= 1.0
    mine, theirs = flat(grads), flat(want_grads)
    for name, g in mine.items():
        if name.endswith("router_bias"):      # a buffer: no gradient
            assert float(jnp.max(jnp.abs(g))) == 0
            continue
        scale = float(jnp.max(jnp.abs(theirs[name])))
        assert float(jnp.max(jnp.abs(g - theirs[name]))) < 1e-3 * scale, name


def test_the_routed_sum_left_out_is_another_model(params, tokens):
    """The planted fault of this family: without the routed sum the loss
    moves by far more than any tolerance above."""
    total, count = ref.loss_sum(params, tokens, HF)
    cut, _ = ref.loss_sum(params, tokens, HF, fault="no_routed")
    assert abs(float(cut - total)) / float(total) > 1e-3


def test_the_share_adds_up_to_the_uncut_layer(params):
    """The guide's share test on the reference itself: the routed parts of
    both shares (experts 0-3 and 4-7 of the router's 8) plus the shared
    expert once are the uncut layer's output."""
    c = ref.dims(HF)
    w = params["layers"]["01"]
    more = kl.init(jax.random.key(4), CFG)["layers"]["01"]
    n = jax.random.normal(jax.random.key(7), (96, 64))
    whole_w = dict(w, **{k: jnp.concatenate([w[k], more[k]])
                         for k in ("e_gate", "e_up", "e_down")})
    whole = ref.ffn(dict(c, held=8), 1, n, whole_w, None)
    shared = ref.swiglu(n, w["s_gate"], w["s_up"], w["s_down"], None)
    parts = (ref.routed(c, n, w, None, first=0)
             + ref.routed(c, n, dict(w, **{k: more[k] for k in (
                 "e_gate", "e_up", "e_down")}), None, first=4))
    assert float(jnp.max(jnp.abs(shared + parts - whole))) < 1e-5 * float(
        jnp.max(jnp.abs(whole)))


# -- latent attention's head sizes through the flash kernels --------------------

def heads(seed, s=256):
    ks = jax.random.split(jax.random.key(seed), 4)
    shape = lambda d: (1, s, 2, d)
    return (jax.random.normal(ks[0], shape(192)),
            jax.random.normal(ks[1], shape(192)),
            jax.random.normal(ks[2], shape(128)),
            jax.random.normal(ks[3], shape(128)))


# float32 inputs, float32 accumulation on both sides: 1e-5 of the largest
@pytest.mark.parametrize("impl", ["pallas", "xla"])
def test_flash_takes_qk_192_beside_v_128(impl, monkeypatch):
    monkeypatch.setattr(flash_pallas, "FORCE_INTERPRET", True)
    q, k, v, w = heads(0)
    f = lambda q, k, v: flash_attention(q, k, v, impl=impl)
    want = mha(q, k, v)
    out = f(q, k, v)
    assert out.shape == (1, 256, 2, 128)
    assert float(jnp.max(jnp.abs(out - want))) < 1e-5 * float(
        jnp.max(jnp.abs(want)))
    got = jax.grad(lambda *a: jnp.sum(f(*a) * w), argnums=(0, 1, 2))(q, k, v)
    ref_g = jax.grad(lambda *a: jnp.sum(mha(*a) * w),
                     argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(got, ref_g):
        assert a.shape == b.shape
        assert float(jnp.max(jnp.abs(a - b))) < 1e-5 * float(
            jnp.max(jnp.abs(b)))
