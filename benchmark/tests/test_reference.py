"""The plain reference against the program at a toy size, on the CPU: the
same weights from the same seed bit for bit, the same logits, the same
three training steps; and the lower precisions (the controls) and the
planted faults read outside the limits the cells hold."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from drivers import http_open_loop as serve
from drivers import jaxjob_window as train
from reference import mistral as ref

from conftest import BENCH

CFG = dict(hidden_size=64, intermediate_size=128, num_attention_heads=8,
           num_key_value_heads=4, vocab_size=512, num_hidden_layers=2,
           rope_theta=1e6, rms_norm_eps=1e-5)


def program_config(**kw):
    from kubeflow_tpu.models import llama

    return llama.LlamaConfig(
        vocab_size=512, d_model=64, n_layers=2, n_heads=8, n_kv_heads=4,
        d_ff=128, max_seq_len=128, rope_theta=1e6, norm_eps=1e-5,
        attention_impl="xla", remat=False, **kw)


def test_init_is_the_programs_bit_for_bit():
    from kubeflow_tpu.models import llama

    mine = train.flat(ref.init_params(7, CFG))
    theirs = train.flat(llama.init(jax.random.key(7), program_config()))
    assert set(theirs) == set(train.LEAF)
    for k, name in train.LEAF.items():
        assert bool((theirs[k] == mine[name]).all()), k


def test_logits_and_loss_agree_with_the_program_in_float32():
    from kubeflow_tpu.models import llama

    lc = program_config(dtype=jnp.float32)
    toks = jnp.asarray(np.random.default_rng(0).integers(1, 512, (2, 32)))
    want = ref.logits(ref.init_params(7, CFG), toks, CFG)
    got = llama.apply(llama.init(jax.random.key(7), lc), toks, lc)
    assert float(jnp.abs(got - want).max()) < 1e-4
    s, n = ref.loss_sum(ref.init_params(7, CFG), toks, CFG)
    loss = llama.loss_fn(llama.init(jax.random.key(7), lc),
                         {"tokens": toks}, lc)[0]
    assert float(abs(loss - s / n)) < 1e-5


def test_loader_twin_gives_the_programs_rows(tmp_path):
    from kubeflow_tpu.training.loader import PyTokenLoader

    path = str(tmp_path / "c.bin")
    corpus = train.write_corpus(path, 5, 5000, 512)
    mine = train.loader_rows(corpus, 5, 4, 64, 3)
    theirs = PyTokenLoader(path, 4, 64, seed=5)
    for rows in mine:
        assert (next(theirs)["tokens"] == rows).all()


def test_served_control_fails_where_the_reference_passes():
    """Greedy tokens of the reference itself read a gap of 0; the tokens
    the int4 model puts first read far over the limit the cell holds."""
    toy = json.load(open(os.path.join(BENCH, "tests", "toy_serve.json")))
    limit = toy["traffic"]["limits"]["served_logit_gap_max"]   # toy's own
    rng = np.random.default_rng(1)
    params = ref.init_params(3, CFG)
    samples = []
    for _ in range(3):
        prompt = rng.integers(1, 512, 24).tolist()
        seq = list(prompt)
        for _ in range(12):   # greedy by the reference
            lg = ref.logits(params, jnp.asarray([seq]), CFG)[0, -1]
            seq.append(int(jnp.argmax(lg)))
        samples.append({"prompt": prompt, "tokens": seq[len(prompt):]})
    sound = serve.served_gaps(CFG, 3, samples, pad_to=64)
    assert sound["widest_gap"] <= 1e-4 < limit
    control = serve.served_gaps(CFG, 3, samples, lower="int4", pad_to=64)
    assert control["widest_gap"] > limit
    # a token altered where it is produced
    samples[0]["tokens"][2] ^= 1
    assert serve.served_gaps(CFG, 3, samples, pad_to=64)["widest_gap"] > limit


def _reference_run(**kw):
    opt = json.load(open(os.path.join(
        BENCH, "traffic", "pretrain_b8_s2048.json")))["optimizer"]
    corpus = np.random.default_rng(2).integers(0, 512, 20000,
                                               dtype=np.uint32)
    rows = train.loader_rows(corpus, 9, 8, 64, 3)
    return train.reference_steps(CFG, 9, rows, opt, jax.devices()[:4], **kw)


def _in_programs_place(out):
    back = {v: k for k, v in train.LEAF.items()}
    return {"loss": out["loss"], "grad_norm": out["grad_norm"],
            "first_grad": {back[k]: v for k, v in out["first_grad"].items()},
            "change": {back[k]: v for k, v in out["change"].items()}}


@pytest.fixture(scope="module")
def sound():
    return _reference_run()


def limits():
    return json.load(open(os.path.join(
        BENCH, "traffic", "pretrain_b8_s2048.json")))["limits"]


def outside(got):
    return [k for k, lim in limits().items() if got[k] > lim]


def test_reference_in_its_own_place_is_inside_every_limit(sound):
    assert not outside(train.compare(_in_programs_place(sound), sound))


@pytest.mark.parametrize("fault", ["half_batch", "no_exchange"])
def test_planted_fault_fails_a_number(sound, fault):
    bad = _reference_run(fault=fault)
    assert outside(train.compare(_in_programs_place(bad), sound))


def test_trained_control_fails_a_number(sound):
    """The reference in fp8 (one step under the trained bfloat16), put in
    the program's place, reads outside a limit the cell holds."""
    low = _reference_run(lower="fp8")
    assert outside(train.compare(_in_programs_place(low), sound))


def test_state_left_unchanged_reads_one(sound):
    frozen = _in_programs_place(sound)
    frozen["change"] = {k: 0.0 for k in frozen["change"]}
    got = train.compare(frozen, sound)
    assert got["param_change_leaf_gap"] == pytest.approx(1.0)
    assert "param_change_leaf_gap" in outside(got)
