"""The training layer's overlapped tensor-parallel projections
(models/llama.py `_overlapped_layer`, parallel/overlap.py):

- on a `fsdp 2 x tensor 2` mesh the loss and every gradient leaf match
  the same layers with no mesh, under full and minimal remat;
- the path is chosen from the mesh and the inputs alone: `tensor` 1, a
  sequence that does not divide, an axis already manual (an enclosing
  shard_map, the pipeline's stage body), a quantised or an adapter leaf
  each take the plain GSPMD code;
- the compiled step on that mesh moves its activations by
  collective-permute, and no all-reduce of a [B, S, D] activation is left
  inside the layer scans (the plain path, as the control, has them);
- the Trainer's first record says how many of a layer's projections
  carry their own exchange.
"""

from __future__ import annotations

import dataclasses
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from kubeflow_tpu.models import llama
from kubeflow_tpu.parallel import (MeshConfig, active_mesh, make_mesh,
                                   overlap, shard_tree,
                                   tree_logical_to_sharding)

SITES = {"wq|wk|wv", "wo", "w_gate|w_up", "w_down"}
B, S = 4, 32
CFG = llama.LlamaConfig(vocab_size=256, d_model=64, n_layers=2, n_heads=8,
                        n_kv_heads=4, d_ff=128, max_seq_len=64,
                        attention_impl="xla", dtype=jnp.float32,
                        remat=True, remat_policy="full")


def _loss_and_grads(cfg):
    def fn(params, batch):
        (loss, _), grads = jax.value_and_grad(
            lambda p: llama.loss_fn(p, batch, cfg), has_aux=True)(params)
        return loss, grads
    return jax.jit(fn)


def _inputs(cfg, seq=S):
    params = llama.init(jax.random.key(0), cfg)
    tokens = jax.random.randint(jax.random.key(1), (B, seq), 0,
                                cfg.vocab_size)
    return params, {"tokens": tokens}


def _on_mesh(mesh, cfg, params, batch):
    params = shard_tree(params, tree_logical_to_sharding(
        llama.logical_axes_for(params, cfg), mesh))
    batch = {"tokens": jax.device_put(
        batch["tokens"], NamedSharding(mesh, P(("data", "fsdp"))))}
    return params, batch


@pytest.mark.parametrize("remat_policy", ["full", "minimal"])
def test_overlapped_layer_matches_the_unsharded_layer(devices8,
                                                      remat_policy):
    cfg = dataclasses.replace(CFG, remat_policy=remat_policy)
    params, batch = _inputs(cfg)
    loss0, grads0 = _loss_and_grads(cfg)(params, batch)
    mesh = make_mesh(MeshConfig(fsdp=2, tensor=2), devices=devices8[:4])
    with active_mesh(mesh), overlap.count_sites() as sites:
        loss1, grads1 = _loss_and_grads(cfg)(
            *_on_mesh(mesh, cfg, params, batch))
    assert sites == SITES
    # the tolerance of tests/test_seq_parallel.py's sharded-against-single
    np.testing.assert_allclose(loss1, loss0, rtol=2e-4, atol=2e-4)
    flat0 = jax.tree_util.tree_leaves_with_path(grads0)
    flat1 = jax.tree.leaves(grads1)
    assert len(flat0) == len(flat1) == 12
    for (path, a), b in zip(flat0, flat1):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), rtol=2e-4,
                                   atol=2e-4,
                                   err_msg=jax.tree_util.keystr(path))


def _traced_sites(mesh, cfg, params, batch):
    with active_mesh(mesh), overlap.count_sites() as sites:
        jax.jit(lambda p, b: llama.loss_fn(p, b, cfg)[0]).lower(
            *_on_mesh(mesh, cfg, params, batch))
    return sites


@pytest.mark.parametrize("case", [
    "engages", "tensor_1", "sequence_does_not_divide",
    "sequence_axis_sharded", "tensor_already_manual",
    "pipeline_stage_body", "quantised_leaf", "adapter_leaf"])
def test_path_selection(devices8, case):
    params, batch = _inputs(CFG)
    fsdp_tp = make_mesh(MeshConfig(fsdp=2, tensor=2), devices=devices8[:4])
    if case == "engages":
        assert _traced_sites(fsdp_tp, CFG, params, batch) == SITES
    elif case == "tensor_1":
        mesh = make_mesh(MeshConfig(fsdp=4), devices=devices8[:4])
        assert _traced_sites(mesh, CFG, params, batch) == set()
    elif case == "sequence_does_not_divide":
        params, batch = _inputs(CFG, seq=S + 1)
        assert _traced_sites(fsdp_tp, CFG, params, batch) == set()
    elif case == "sequence_axis_sharded":
        mesh = make_mesh(MeshConfig(sequence=2, tensor=2),
                         devices=devices8[:4])
        assert _traced_sites(mesh, CFG, params, batch) == set()
    elif case == "tensor_already_manual":
        raw = [params["layers"][t][0] for t in llama.QUANT_LEAVES]
        seen = []

        def body(x):
            seen.append(overlap.mesh_for(S, raw))
            return x

        with active_mesh(fsdp_tp):
            assert overlap.mesh_for(S, raw) is fsdp_tp
            jax.jit(jax.shard_map(
                body, mesh=fsdp_tp, in_specs=P(None, "tensor"),
                out_specs=P(None, "tensor"),
                axis_names=frozenset({"tensor"}))).lower(
                jnp.zeros((B, S)))
        assert seen == [None]
    elif case == "pipeline_stage_body":
        mesh = make_mesh(MeshConfig(stage=2, tensor=2),
                         devices=devices8[:4])
        assert _traced_sites(mesh, CFG, params, batch) == set()
    elif case == "quantised_leaf":
        quantised = llama.quantize_params(params)
        with active_mesh(fsdp_tp), overlap.count_sites() as sites:
            jax.jit(lambda p, t: llama.apply(p, t, CFG)).lower(
                *_on_mesh(fsdp_tp, CFG, quantised, batch)[:1],
                batch["tokens"])
        assert sites == set()
    else:   # a LoRA pair where the merged weight would be
        layer = [params["layers"][t][0] for t in llama.QUANT_LEAVES]
        layer[0] = {"a": jnp.zeros((64, 4)), "b": jnp.zeros((4, 64))}
        with active_mesh(fsdp_tp):
            assert overlap.mesh_for(S, layer) is None


def _scan_bodies(hlo: str) -> list[str]:
    """The text of every computation that is the body of a `while`."""
    names = set(re.findall(r"\bbody=%?([\w.\-]+)", hlo))
    blocks = re.split(r"\n(?=(?:ENTRY )?%?[\w.\-]+ \([^\n]*\) -> [^\n]*\{\n)",
                      hlo)
    out = [b for b in blocks
           if re.match(r"(?:ENTRY )?%?([\w.\-]+) ", b).group(1) in names]
    assert out
    return out


@pytest.mark.parametrize("path", ["overlapped", "plain"])
def test_compiled_step_moves_activations_by_permute(devices8, monkeypatch,
                                                    path):
    """In the layer scans of the compiled loss-and-gradient step: the
    overlapped path holds collective-permutes and no all-reduce of a
    [batch, S, features] activation, however the partitioner shards the
    batch and the features; the plain path, the control, holds such
    all-reduces (so the pattern does see them)."""
    if path == "plain":
        monkeypatch.setattr(overlap, "mesh_for", lambda *a: None)
    params, batch = _inputs(CFG)
    mesh = make_mesh(MeshConfig(fsdp=2, tensor=2), devices=devices8[:4])
    with active_mesh(mesh):
        hlo = _loss_and_grads(CFG).lower(
            *_on_mesh(mesh, CFG, params, batch)).compile().as_text()
    bodies = "\n".join(_scan_bodies(hlo))
    activation = rf"f32\[\d+,{S},\d+\]"
    reduced = re.findall(rf"= {activation}\S* all-reduce(?:-start)?\(",
                         bodies)
    permutes = re.findall(r" collective-permute(?:-start)?\(", bodies)
    if path == "overlapped":
        assert not reduced and len(permutes) >= 8
    else:
        assert len(reduced) >= 4 and not permutes


# sequence, attention body -> the share of the visited attention tiles that
# run with no mask: none without the kernels; at 256 the kernels' one tile
# a head is the diagonal's; at 2048 with the default 1024 x 1024 tiles 1 of 3
@pytest.mark.parametrize("seq,impl,share", [
    (S, "xla", 0.0), (256, "flash", 0.0), (2048, "flash", 1 / 3)])
@pytest.mark.parametrize("mesh_cfg,want", [
    (MeshConfig(fsdp=2, tensor=2), 4.0), (MeshConfig(fsdp=4), 0.0)])
def test_trainer_first_record_counts_the_overlapped_projections(
        devices8, monkeypatch, mesh_cfg, want, seq, impl, share):
    from kubeflow_tpu.ops import flash_pallas
    from kubeflow_tpu.training import (OptimizerConfig, Trainer,
                                       TrainerConfig)
    from kubeflow_tpu.training import data as data_lib

    monkeypatch.setattr(flash_pallas, "FORCE_INTERPRET", True)
    tr = Trainer(TrainerConfig(
        model="llama", batch_size=B, mesh=mesh_cfg, log_every=1,
        optimizer=OptimizerConfig(warmup_steps=2, total_steps=10),
        model_overrides={"vocab_size": 256, "d_model": 32, "n_layers": 2,
                         "n_heads": 4, "n_kv_heads": 2, "d_ff": 64,
                         "max_seq_len": seq, "attention_impl": impl}),
        devices=devices8[:4])
    tr.metrics.echo = False
    records = []
    tr.train(data_lib.for_model("llama", tr.model_cfg, B, seq_len=seq), 2,
             step_callback=lambda step, m: records.append(m))
    assert records[0]["overlapped_projections_per_layer"] == want
    assert records[0]["attention_interior_tile_share"] == share
    assert not {"overlapped_projections_per_layer",
                "attention_interior_tile_share"} & set(records[1])
