"""Serving contract proof: Llama-3-8B InferenceService on a v5e slice.

BASELINE config #5 is "InferenceService: Llama-3-8B"; no 8-chip slice exists
on a dev box, so — exactly like training/contract.py for config #3 — the
contract is proven against the REAL v5e compiler via PJRT topology AOT:

  - Build the engine's program menu (batched prefill wave + chained decode
    chunk — the same unbound methods LLMEngine compiles at runtime) at the
    true 8B dimensions, with params sharded by the model's logical axes and
    the KV cache sharded over kv-heads on a tensor=8 mesh.
  - AOT-compile each program for the v5e target and read XLA's buffer
    assignment: compile() itself enforces the HBM budget (RESOURCE_EXHAUSTED
    on an oversubscribed layout), and memory_analysis() reports the heap
    peak per device.
  - Account weights + KV cache residency analytically from the shardings.

Variants: weights as bf16 and weight-only int8 (ops/quant per-channel — the
production decode configuration).

Reference anchor (SURVEY.md §2.4 KServe + §2.6 Triton-class runtime row):
the reference serves 8B-class LLMs through kserve runtimes on GPU pools;
here the same contract is a mesh + logical-axis rules on the engine's
static program menu.
"""

from __future__ import annotations

import functools
import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from kubeflow_tpu.models import llama
from kubeflow_tpu.parallel.mesh import active_mesh
from kubeflow_tpu.serving.llm import LLMEngine, pin_attention_impls

V5E_HBM_BYTES = 16 * 1024**3


class _AbstractEngine(LLMEngine):
    """An LLMEngine with no device state: just the attributes its program
    methods read, so they can be traced from ShapeDtypeStructs. Being a
    subclass, it traces the SAME program bodies, class flags and helpers
    the live engine jits — the proof covers the production code path and
    cannot fall behind it."""

    def __init__(self, cfg: llama.LlamaConfig, kv_quantize: str | None = None,
                 *, n_slots: int = 0, max_len: int = 0,
                 speculative: int | None = None, adapters: bool = False,
                 logprobs_topk: int = 0):
        # deliberately NOT LLMEngine.__init__: that allocates the cache
        self.cfg = cfg
        self.mesh = None
        self.kv_quantize = kv_quantize
        # spec mode swaps the decode program for _spec_decode and adapters
        # add a rank-r gathered bypass to every matmul; both variants are
        # compiled by aot_serving_report when requested (r3 advisor: the
        # exclusion used to be asserted, not proven)
        self.spec = speculative
        self.spec_ngram = 3
        self.n_slots = n_slots
        self.max_len = max_len
        self.adapters = True if adapters else None
        self._row_extra = 9 if adapters else 8
        # production sampler default (serving/llm.py __init__)
        self.sample_k_max = 64
        self.logprobs_topk = logprobs_topk


def _abstract_tree(tree, shardings):
    return jax.tree.map(
        lambda l, sh: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=sh),
        tree, shardings)


def _leaf_device_bytes(leaf) -> int:
    shard = leaf.sharding.shard_shape(leaf.shape)
    return math.prod(shard) * leaf.dtype.itemsize


def _peak(compiled) -> int:
    ma = compiled.memory_analysis()
    if ma is None:
        return 0
    peak = getattr(ma, "peak_memory_in_bytes", 0) or (
        ma.argument_size_in_bytes + ma.temp_size_in_bytes)
    return int(peak)


def aot_serving_report(
    topology: str | None = "v5e:2x4",
    *,
    quantize: str | None = None,
    kv_quantize: str | None = None,
    n_devices: int = 8,
    tensor: int | None = None,
    n_slots: int = 8,
    max_len: int = 8192,
    bucket: int = 2048,
    width: int = 4,
    decode_steps: int = 8,
    do_compile: bool = True,
    model_overrides: dict[str, Any] | None = None,
    speculative: int | None = None,
    n_adapters: int = 0,
    adapter_rank: int = 16,
    logprobs_topk: int = 0,
) -> dict[str, Any]:
    """Compile the engine's program menu for a v5e target; return the
    memory evidence and which kernels each program carries.
    `topology=None` targets `n_devices` local devices instead (the CI
    virtual-CPU path). `tensor` is the width of the engine's mesh over the
    target's first devices (default: all of them); `tensor=1` is the
    single-chip engine — no mesh, attention and int8-matmul kernels
    selected exactly as a process on that chip would select them."""
    from kubeflow_tpu.parallel import MeshConfig
    from kubeflow_tpu.parallel.mesh import make_mesh
    from kubeflow_tpu.parallel.sharding import tree_logical_to_sharding

    if topology is not None:
        from jax.experimental import topologies

        devices = list(topologies.get_topology_desc(topology).devices)
    else:
        devices = jax.devices()[:n_devices]
    tensor = len(devices) if tensor is None else tensor
    sharded = tensor > 1
    overrides = dict(model_overrides or {})
    cfg = (llama.LlamaConfig.llama3_8b() if model_overrides is None
           else llama.LlamaConfig(**overrides))
    if cfg.n_kv_heads % tensor:
        raise ValueError(f"kv heads {cfg.n_kv_heads} vs tensor={tensor}")
    mesh = make_mesh(MeshConfig(tensor=tensor), devices=devices[:tensor])
    with active_mesh(mesh):   # the target platform the impls resolve for
        cfg = pin_attention_impls(cfg, sharded=sharded)

    cache_sh = NamedSharding(mesh, P(None, None, None, "tensor"))
    repl = NamedSharding(mesh, P())
    # per-slot penalty counts ride the cache vocab-sharded over `tensor`,
    # exactly the live engine's layout (_shard_over: _cnt_sh)
    cnt_sh = NamedSharding(mesh, P(None, "tensor"))

    def engine(**kw) -> _AbstractEngine:
        eng = _AbstractEngine(cfg, kv_quantize=kv_quantize, n_slots=n_slots,
                              max_len=max_len, logprobs_topk=logprobs_topk,
                              **kw)
        if sharded:   # the live engine's mesh + count-layout constraint
            eng.mesh, eng._cnt_sh = mesh, cnt_sh
        return eng

    def lower(program, *args, donate=(1, 2, 3, 4, 5)):
        # ambient mesh: a single-chip engine has none of its own, and an
        # AOT trace from a CPU process must still see the TPU target
        with active_mesh(mesh):
            return jax.jit(program, donate_argnums=donate).lower(*args)

    eng = engine()

    # one abstract trace of the full init, shared by the weight shardings,
    # the adapter target dims, and the n_params count
    init_sds = jax.eval_shape(lambda: llama.init(jax.random.key(0), cfg))

    # -- weights: bf16 (cast) or weight-only int8, sharded by logical axes
    def build_params(p):
        p = jax.tree.map(
            lambda x: x.astype(jnp.bfloat16)
            if jnp.issubdtype(x.dtype, jnp.floating) else x, p)
        if quantize == "int8":
            p = llama.quantize_params(p)
        return p

    p_sds = jax.eval_shape(build_params, init_sds)
    p_sh = tree_logical_to_sharding(
        llama.logical_axes_for(p_sds, cfg), mesh)
    params = _abstract_tree(p_sds, p_sh)

    # cache schema from the ONE source of truth (llama.init_cache) so the
    # proof can't drift from the layout the live engine allocates
    cache = {
        name: jax.ShapeDtypeStruct(
            sds.shape, sds.dtype,
            sharding=NamedSharding(mesh, llama.cache_kv_spec(name)))
        for name, sds in jax.eval_shape(
            lambda: llama.init_cache(cfg, n_slots, max_len,
                                     kv_quantize=kv_quantize)).items()}
    cache["cnt"] = jax.ShapeDtypeStruct((n_slots, cfg.vocab_size),
                                        jnp.int32, sharding=cnt_sh)
    i32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.int32,
                            sharding=repl)
    lengths, last = i32((n_slots,)), i32((n_slots,))
    # per-slot sampling state [temperature, top_k, top_p, presence,
    # frequency, seed]
    samp = jax.ShapeDtypeStruct((n_slots, 6), jnp.float32, sharding=repl)
    key_sds = jax.eval_shape(lambda: jax.random.key(0))
    key = jax.ShapeDtypeStruct(key_sds.shape, key_sds.dtype, sharding=repl)
    wave = i32((width, bucket + 8))
    active = jax.ShapeDtypeStruct((n_slots,), jnp.bool_, sharding=repl)
    hist = i32((n_slots, max_len))
    aids = i32((n_slots,))
    state = (lengths, last, samp, key)

    # chunked-prefill / prefix-cache continuation steps. Every chain
    # boundary compiles a DIFFERENT (p, t) program with a growing prefix
    # tensor, so the contract covers the FIRST boundary (p=bucket — the
    # prefix-cache hit shape) and the LARGEST possible boundary
    # (p = max_len - bucket — the worst-peak program of the longest
    # admissible prompt), plus the extract feeding it.
    def kv_prefix(p):
        return jax.ShapeDtypeStruct(
            (cfg.n_layers, 1, p, cfg.n_kv_heads, cfg.head_dim),
            jnp.dtype(cfg.dtype), sharding=cache_sh)

    p_max = max_len - bucket
    cont_wave = i32((1, bucket + 8))
    lowered: dict[str, Any] = {
        f"prefill_b{bucket}_w{width}":
            lower(eng._prefill, params, cache, *state, wave),
        f"decode_x{decode_steps}":
            lower(functools.partial(eng._decode, steps=decode_steps),
                  params, cache, *state, active),
        f"cont_p{bucket}_t{bucket}":
            lower(eng._prefill_cont, params, cache, *state, cont_wave,
                  kv_prefix(bucket), kv_prefix(bucket)),
        f"cont_p{p_max}_t{bucket}":
            lower(eng._prefill_cont, params, cache, *state, cont_wave,
                  kv_prefix(p_max), kv_prefix(p_max)),
        f"extract_p{p_max}":
            lower(functools.partial(eng._extract_prefix, p=p_max), cache,
                  jax.ShapeDtypeStruct((), jnp.int32, sharding=repl),
                  donate=()),
    }

    if speculative:
        # the speculative verify program (scan of _spec_decode rounds) at
        # full span — the worst-HBM member of the spec menu: its verify
        # forward carries S_v = spec+1 query rows plus the history buffer
        lowered[f"spec_k{speculative}_x{decode_steps}"] = lower(
            functools.partial(engine(speculative=speculative)._spec_decode,
                              steps=decode_steps, span=max_len),
            params, dict(cache, hist=hist), *state, active)
    if n_adapters:
        # multi-adapter serving: the adapter stack rides as a trailing
        # program arg ([L, A+1, ...] per target, index 0 = zero adapter)
        # and the cache carries per-slot adapter ids. Target dims come from
        # the model's own (unquantized) layer leaves — one source of truth
        # for the layout, exactly like lora.init reads them.
        ad_eng = engine(adapters=True)
        lora = {}
        for t in ("wq", "wk", "wv", "wo"):
            _, di, do = init_sds["layers"][t].shape
            lora[t] = {"a": jax.ShapeDtypeStruct(
                           (cfg.n_layers, n_adapters + 1, di, adapter_rank),
                           jnp.float32, sharding=repl),
                       "b": jax.ShapeDtypeStruct(
                           (cfg.n_layers, n_adapters + 1, adapter_rank, do),
                           jnp.float32, sharding=repl)}
        ad_cache = dict(cache, aids=aids)
        tag = f"a{n_adapters}_r{adapter_rank}"
        lowered[f"adapter_prefill_{tag}"] = lower(
            ad_eng._prefill, params, ad_cache, *state,
            i32((width, bucket + 9)), lora)
        lowered[f"adapter_decode_{tag}"] = lower(
            functools.partial(ad_eng._decode, steps=decode_steps,
                              span=max_len),
            params, ad_cache, *state, active, lora)
        if speculative:
            # the live engine dispatches spec AND adapters in ONE program
            # (_do_decode's spec branch passes the adapter stack into
            # _spec_decode);
            # the combined member carries the spec+1 query rows, the hist
            # buffer, and the gathered rank-r bypass simultaneously — it,
            # not either variant alone, is the true worst of this menu
            lowered[
                f"spec_k{speculative}_adapter_a{n_adapters}_x{decode_steps}"
            ] = lower(
                functools.partial(
                    engine(speculative=speculative,
                           adapters=True)._spec_decode,
                    steps=decode_steps, span=max_len),
                params, dict(ad_cache, hist=hist), *state, active, lora)

    if speculative or n_adapters:
        # the worst-peak member of the BASE menu is the largest-boundary
        # continuation (cont_p_max); its spec/adapter variant — extra
        # prefix-token wave columns + hist writes under spec, the gathered
        # rank-r bypass under adapters — is the true worst of the combined
        # menu, so it must be compiled too, not asserted to ride the margin
        worst_cache = dict(cache)
        if speculative:
            worst_cache["hist"] = hist
        if n_adapters:
            worst_cache["aids"] = aids
        ex = 9 if n_adapters else 8
        worst_args = (params, worst_cache, *state,
                      i32((1, bucket + (p_max if speculative else 0) + ex)),
                      kv_prefix(p_max), kv_prefix(p_max))
        if n_adapters:
            worst_args = worst_args + (lora,)
        worst_name = (f"cont_p{p_max}_t{bucket}"
                      + (f"_spec{speculative}" if speculative else "")
                      + (f"_a{n_adapters}" if n_adapters else ""))
        lowered[worst_name] = lower(
            engine(speculative=speculative,
                   adapters=bool(n_adapters))._prefill_cont, *worst_args)

    weight_bytes = sum(_leaf_device_bytes(l) for l in jax.tree.leaves(params))
    # KV bytes proper; the penalty-count buffer is auxiliary slot state,
    # itemized separately so the KV accounting stays exact
    cache_bytes = sum(_leaf_device_bytes(v) for n, v in cache.items()
                      if n != "cnt")
    report: dict[str, Any] = {
        "model": ("llama3-8b" if model_overrides is None
                  else f"llama-custom(d{cfg.d_model}xL{cfg.n_layers})"),
        "n_params": sum(
            math.prod(l.shape) for l in jax.tree.leaves(init_sds)),
        "target": topology or str(devices[0].platform),
        "n_devices": tensor,
        "tensor_parallel": tensor,
        "weights": quantize or "bf16",
        "kv_cache": kv_quantize or str(jnp.dtype(cfg.dtype)),
        "decode_attention_impl": cfg.decode_attention_impl,
        "prefill_attention_impl": cfg.prefill_attention_impl,
        "n_slots": n_slots,
        "max_len": max_len,
        "prefill_bucket": bucket,
        "wave_width": width,
        "decode_steps": decode_steps,
        "speculative": speculative,
        "n_adapters": n_adapters,
        "weight_bytes_per_device": weight_bytes,
        "kv_cache_bytes_per_device": cache_bytes,
        "aux_state_bytes_per_device": _leaf_device_bytes(cache["cnt"]),
        # Mosaic custom calls in each program's StableHLO: which programs
        # the Pallas kernels (attention, int8 matmul) actually reached
        "mosaic_calls": {name: low.as_text().count("tpu_custom_call")
                         for name, low in lowered.items()},
        "lowered": True,
    }
    if do_compile:
        peaks = {name: _peak(low.compile())
                 for name, low in lowered.items()}
        report["compiled"] = True
        report["peak_bytes_per_device"] = peaks
        worst = max(peaks.values())
        report["worst_peak_bytes_per_device"] = worst
        report["v5e_hbm_bytes"] = V5E_HBM_BYTES
        report["fits_v5e_hbm"] = bool(worst <= V5E_HBM_BYTES)
    return report


if __name__ == "__main__":
    import json

    for q in (None, "int8"):
        print(json.dumps(aot_serving_report(quantize=q)))
