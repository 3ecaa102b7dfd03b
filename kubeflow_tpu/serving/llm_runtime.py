"""LLM serving runtime — registers modelFormat "llama" (and every other
served model family, FAMILIES below: one LLMModel class, differing in the
model module and the config class it hands the engine) so an
InferenceService predictor resolves to the continuous-batching engine
(SURVEY.md §2.4 runtime table: the huggingfaceserver/Triton-LLM slot).

    kind: InferenceService
    spec:
      predictor:
        model:
          modelFormat: llama
          config:
            model: {d_model: ..., n_layers: ...}   # LlamaConfig overrides
            n_slots: 4
            max_len: 512
            buckets: [64, 128, 256]
            checkpoint: /path/to/orbax/dir         # optional params source

V1/V2 payload: {"prompt_tokens": [...], "max_new_tokens": N} (or a list of
those). The engine thread runs continuous batching underneath, so
concurrent HTTP requests share decode steps; per-request TTFT lands in
Model.metrics() for the KServe-TTFT baseline metric (config #5).

Unified dataplane (ISSUE 12): by default the engine sits behind an
`EngineSupervisor` — every HTTP/SSE/gRPC/predict submission is
journaled, a mid-stream engine crash or stall triggers
journal→restart→idempotent replay while the SSE connection stays open
(keepalive comments during the restart window), and token emission
resumes from the journaled prefix with zero duplicate and zero lost
tokens. Greedy/seeded output through a crash is byte-identical to an
uncrashed run (the supervisor verifies the replayed prefix).
"""

from __future__ import annotations

import dataclasses
import threading
import time
from typing import Any

from kubeflow_tpu.serving.model import Model, serving_runtime

# jax and the family's model module are imported inside load()/
# _load_params() so that registering this runtime (imported by
# kubeflow_tpu.serving for its side effect) keeps the serving package
# import jax-free.


@dataclasses.dataclass(frozen=True)
class Family:
    """A served model family: the module that carries the engine's seam
    (serving/llm.py, "THE FAMILY SEAM") and `init`, the config class in
    it, and the deployment options the family does not serve, each with
    the reason a load that asks for it is refused with."""
    module: str
    config_cls: str
    refuses: dict[str, str] = dataclasses.field(default_factory=dict)


FAMILIES: dict[str, Family] = {
    "llama": Family("kubeflow_tpu.models.llama", "LlamaConfig"),
    "laguna": Family("kubeflow_tpu.models.laguna", "LagunaConfig", refuses={
        "speculative": "a window layer's ring cannot take back the rows "
                       "of rejected drafts: the family has no verify step",
        "prefix_cache": "the radix cache holds full-attention blocks; a "
                        "window layer's ring keeps no prefix's tail",
        "kv_layout": "the block pool has one layout for every layer, the "
                     "family two slabs (full, window)",
        "parallel": "the experts have no layout across chips (no exchange)",
        "mesh": "the experts have no layout across chips (no exchange)",
        "adapters": "the family's matmuls take no low-rank bypass",
        "lora": "the family's matmuls take no low-rank bypass",
        "quantize": "int8 experts need a grouped matmul that dequantizes "
                    "its groups",
        "disaggregated": "the prefill->decode handoff moves one slab's "
                         "rows, the family has two",
    }),
    "pangu_ultra_moe": Family(
        "kubeflow_tpu.models.pangu_ultra_moe", "PanguUltraMoEConfig",
        refuses={
            "speculative": "the drafter would be the MTP module, which is "
                           "not served: it needs the verified hidden "
                           "state, a second latent cache and a verify "
                           "step, and drafts nothing a verifier accepts "
                           "from seeded weights",
            "prefix_cache": "the radix cache holds [kv, head_dim] blocks, "
                            "the family one latent row a token",
            "kv_layout": "the block pool holds [kv, head_dim] blocks, the "
                         "family one latent row a token",
            "parallel": "the experts held here have no exchange with the "
                        "other ranks' (one chip of an expert-parallel "
                        "layer)",
            "mesh": "the experts held here have no exchange with the other "
                    "ranks' (one chip of an expert-parallel layer)",
            "adapters": "the family's matmuls take no low-rank bypass",
            "lora": "the family's matmuls take no low-rank bypass",
            "quantize": "int8 experts need a grouped matmul that "
                        "dequantizes its groups",
            "disaggregated": "the prefill->decode handoff moves [kv, "
                             "head_dim] rows, the family latent rows",
        }),
    "nemotron_h": Family(
        "kubeflow_tpu.models.nemotron_h", "NemotronHConfig", refuses={
            "speculative": "a recurrent state cannot take back the "
                           "positions of rejected drafts without a "
                           "snapshot of it, and the drafter would be the "
                           "MTP module, which is not served",
            "prefix_cache": "the radix cache holds [kv, head_dim] blocks, "
                            "not the recurrent states a prefix leaves",
            "kv_layout": "the block pool holds [kv, head_dim] blocks, not "
                         "recurrent states",
            "parallel": "the experts held here have no exchange with the "
                        "other ranks' (one chip of an expert-parallel "
                        "layer)",
            "mesh": "the experts held here have no exchange with the other "
                    "ranks' (one chip of an expert-parallel layer)",
            "adapters": "the family's matmuls take no low-rank bypass",
            "lora": "the family's matmuls take no low-rank bypass",
            "quantize": "int8 experts need a grouped matmul that "
                        "dequantizes its groups",
            "disaggregated": "the prefill->decode handoff moves KV rows, "
                             "not recurrent states",
        }),
}


class LLMModel(Model):
    def __init__(self, name: str, uri: str | None = None, *,
                 model: dict[str, Any] | None = None, n_slots: int = 4,
                 max_len: int = 512, buckets=(64, 128, 256),
                 eos_id: int | None = None, checkpoint: str | None = None,
                 seed: int = 0, timeout_s: float = 300.0,
                 mesh: dict[str, int] | None = None,
                 tokenizer: str | None = None,
                 prefix_cache: bool = False, max_prefixes: int = 4,
                 prefix_cache_blocks: int | None = None,
                 decode_chunk: int = 8,
                 quantize: str | None = None,
                 kv_quantize: str | None = None,
                 decode_attention_impl: str | None = None,
                 prefill_attention_impl: str | None = None,
                 speculative: int | None = None,
                 spec_ngram: int = 3,
                 spec_adaptive: bool = True,
                 lora: dict[str, Any] | None = None,
                 adapters: dict[str, Any] | None = None,
                 logprobs_topk: int = 0,
                 sample_k_max: int = 64,
                 pipeline_decode: bool = True,
                 supervised: bool = True,
                 supervisor: dict[str, Any] | None = None,
                 sse_keepalive_s: float = 15.0,
                 disaggregated: bool = False,
                 disagg: dict[str, Any] | None = None,
                 usage_timing: bool = False,
                 kv_layout: str | None = None,
                 pool_blocks: int | None = None,
                 parallel: dict[str, Any] | None = None,
                 trace_sample_rate: float | None = None,
                 slo: dict[str, Any] | None = None,
                 prefill_wave_max: int | None = None,
                 warm_chain: bool = False,
                 family: str = "llama",
                 **_ignored: Any):
        super().__init__(name)
        self._family = FAMILIES[family]
        asked = dict(speculative=speculative, prefix_cache=prefix_cache,
                     kv_layout=kv_layout not in (None, "slab"),
                     parallel=parallel, mesh=mesh, adapters=adapters,
                     lora=lora, quantize=quantize,
                     disaggregated=disaggregated)
        for option, why in self._family.refuses.items():
            if asked[option]:
                raise ValueError(
                    f"modelFormat {family!r} does not serve `{option}`: "
                    f"{why}")
        self._cfg_overrides = dict(model or {})
        self._mesh = dict(mesh) if mesh else None
        # text endpoints (/openai/v1/completions): byte-level fallback or
        # a local HF tokenizer dir (config.tokenizer)
        from kubeflow_tpu.serving.tokenizer import load_tokenizer

        self.tokenizer = load_tokenizer(tokenizer)
        self._n_slots = n_slots
        self._max_len = max_len
        self._buckets = tuple(buckets)
        self._eos_id = eos_id
        self._checkpoint = checkpoint or uri
        self._prefix_cache = prefix_cache
        self._max_prefixes = max_prefixes
        # config.prefix_cache_blocks: radix KV-reuse block-pool capacity
        # (None derives from max_prefixes — see LLMEngine)
        self._prefix_cache_blocks = prefix_cache_blocks
        self._decode_chunk = decode_chunk
        self._quantize = quantize
        self._kv_quantize = kv_quantize
        # config.decode_attention_impl (ISSUE 15): "xla" | "flash" |
        # "auto" — the serving decode/verify attention kernel selection.
        # It is a LlamaConfig field, so `model: {decode_attention_impl:
        # ...}` works too; this top-level key is the ergonomic spelling
        # (and wins over the model dict when both are given). "auto"
        # (the default) resolves flash on TPU / xla elsewhere.
        if decode_attention_impl is not None:
            self._cfg_overrides["decode_attention_impl"] = \
                decode_attention_impl
        # config.prefill_attention_impl (ISSUE 20): the chunked-prefill
        # twin — same spelling rules as decode_attention_impl.
        if prefill_attention_impl is not None:
            self._cfg_overrides["prefill_attention_impl"] = \
                prefill_attention_impl
        self._speculative = speculative
        self._spec_ngram = spec_ngram
        # config.spec_adaptive (default on): per-slot EMA acceptance
        # adapts the draft length k per verify round (serving/llm.py
        # AdaptiveDraftLen); off = static k, the pre-r6 behavior
        self._spec_adaptive = spec_adaptive
        # config.lora {rank, alpha, targets?}: the checkpoint is a
        # llama_lora fine-tune ({"base","lora"} tree); restore it and serve
        # the MERGED model — zero serving-path overhead, the engine never
        # knows LoRA existed
        self._lora = dict(lora) if lora else None
        # config.adapters {name: {checkpoint: <llama_lora ckpt dir>,
        # rank: r, alpha: a}}: multi-adapter serving — each request picks
        # an adapter ("adapter" in the payload), all share the base and
        # the continuous batch
        self._adapters_cfg = dict(adapters) if adapters else None
        self._logprobs_topk = logprobs_topk
        self._sample_k_max = sample_k_max
        self._pipeline_decode = pipeline_decode
        # config.prefill_wave_max: the most prompts one batched prefill
        # program takes (None: every slot); see LLMEngine
        self._prefill_wave_max = prefill_wave_max
        # config.warm_chain: warm-up also compiles the chunked-prefill
        # chain (prompts longer than the largest bucket); see LLMEngine
        self._warm_chain = bool(warm_chain)
        # config.supervised (default ON — the unified-dataplane contract):
        # the engine sits behind serving/agent.EngineSupervisor, so every
        # HTTP/gRPC/predict submission is journaled and a mid-stream
        # engine crash replays instead of killing the client connection.
        # config.supervisor tunes it: {stall_timeout_s, stall_min_steps,
        # backoff_base_s, backoff_cap_s, max_restarts, stability_s,
        # rewarm}. rewarm (default True) re-runs the full warmup menu on
        # every restart — recovery is slower but no live request ever
        # waits on XLA; rewarm=False restarts cold and lets the replay
        # compile only the programs it touches (the fast-lane setting).
        self._supervised = supervised
        self._sup_cfg = dict(supervisor or {})
        # config.disaggregated (ISSUE 13): split serving into a
        # dedicated PREFILL worker (chunked prefill → radix KV blocks,
        # never a decode step) and a DECODE worker (admits via KV
        # handoff, never a full prefill in steady state), each behind
        # its own EngineSupervisor, coordinated by
        # serving/disagg.DisaggregatedEngine. config.disagg tunes it:
        # {handoff: zero_copy|serialized, prefill_slots: N,
        #  max_inflight_prefills: N}.
        self._disaggregated = bool(disaggregated)
        self._disagg_cfg = dict(disagg or {})
        if self._disaggregated and not supervised:
            raise ValueError(
                "disaggregated serving requires supervised: true (each "
                "role's crash story IS its supervisor)")
        # config.parallel {tensor: T, stage: P} (ISSUE 14): the tp×pp
        # engine layout. stage > 1 builds the stage-sharded engine
        # (serving/multichip.py) — per-stage params/KV slabs, microbatched
        # MPMD decode; stage == 1 with tensor > 1 is sugar for the
        # existing GSPMD tensor-parallel mesh path.
        self._parallel = dict(parallel or {})
        _pp_raw = self._parallel.get("stage")
        _tp_raw = self._parallel.get("tensor")
        pp = 1 if _pp_raw is None else int(_pp_raw)
        tp = 1 if _tp_raw is None else int(_tp_raw)
        if pp < 1 or tp < 1:
            raise ValueError("parallel.stage/parallel.tensor must be >= 1")
        if pp > 1 and self._disaggregated:
            raise ValueError(
                "parallel.stage > 1 does not compose with disaggregated "
                "serving yet (the stage pipeline IS the prefill/decode "
                "overlap mechanism)")
        if (pp > 1 or tp > 1) and self._mesh:
            # a silently-dropped tensor request would serve on an
            # unintended layout — reject every parallel+mesh combo
            raise ValueError("pass parallel OR mesh, not both")
        self._pp, self._tp = pp, tp
        if pp == 1 and tp > 1:
            self._mesh = {"tensor": tp}
        # config.kv_layout (ISSUE 19): "slab" (the preallocated
        # [n_slots, max_len] rows — the default) or "paged"
        # (block-granular pool + per-slot block tables with
        # oversubscribed admission, serving/paged.py); unset resolves
        # slab. config.pool_blocks sizes the paged pool (None = the
        # slab's exact HBM footprint).
        resolved = kv_layout or "slab"
        if resolved not in ("slab", "paged"):
            raise ValueError(
                f"kv_layout must be 'slab' or 'paged', got {resolved!r}")
        if resolved == "paged":
            if pp > 1:
                raise ValueError(
                    "kv_layout=paged does not compose with "
                    "parallel.stage > 1 yet: the stage-sharded engine "
                    "keeps per-stage KV slabs (serving/multichip.py)")
            if self._mesh:
                raise ValueError(
                    "kv_layout=paged does not compose with a mesh yet: "
                    "the block pool has no GSPMD layout")
            if self._disaggregated:
                raise ValueError(
                    "kv_layout=paged does not compose with disaggregated "
                    "serving yet: the prefill->decode handoff moves slab "
                    "rows (serving/disagg.py)")
        self._kv_layout = resolved
        self._pool_blocks = pool_blocks
        # config.usage_timing: surface the request_timing() phase split
        # (queue_wait_ms / prefill_ms / decode_ms) in the OpenAI usage
        # object; off (default) keeps the usage shape byte-unchanged
        self._usage_timing = bool(usage_timing)
        # config.sse_keepalive_s: max silence on a token stream before a
        # `: keepalive` SSE comment goes out — during a crash-restart
        # window the connection stays provably alive instead of tripping
        # client/proxy read timeouts
        self._sse_keepalive_s = float(sse_keepalive_s)
        # config.trace_sample_rate (ISSUE 17): fraction of trace ids the
        # process keeps spans for (deterministic per-id hash, so router/
        # supervisor/engine agree without coordination). None leaves the
        # process tracer's current rate alone — the tracer is
        # process-global, so only an explicit config value touches it.
        if trace_sample_rate is not None:
            from kubeflow_tpu.obs.trace import TRACER

            TRACER.set_sample_rate(float(trace_sample_rate))
        # config.slo {ttft_ms, tpot_ms, window_s, budget}: the online
        # burn tracker behind /healthz's "slo" section and the
        # slo_attainment / slo_burn_rate gauges
        from kubeflow_tpu.obs.metrics import add_scrape_hook
        from kubeflow_tpu.obs.slo import SloBurnTracker

        slo_cfg = dict(slo or {})
        self.slo_tracker = SloBurnTracker(
            ttft_slo_ms=float(slo_cfg.get("ttft_ms", 2000.0)),
            tpot_slo_ms=float(slo_cfg.get("tpot_ms", 200.0)),
            window_s=float(slo_cfg.get("window_s", 300.0)),
            budget=float(slo_cfg.get("budget", 0.01)))
        add_scrape_hook(self.slo_tracker, SloBurnTracker.publish)
        self._seed = seed
        self._timeout_s = timeout_s
        self._engine = None
        self._thread: threading.Thread | None = None
        self._stop = threading.Event()
        self._wake = threading.Event()
        self._loop_error: BaseException | None = None
        # rids whose waiter gave up (timeout/error) while still in flight;
        # the engine thread releases them once they finish — a waiter thread
        # must never release an unfinished request out from under the loop
        self._abandoned: set[int] = set()

    # -- lifecycle -----------------------------------------------------------

    def _family_module(self):
        import importlib

        return importlib.import_module(self._family.module)

    def load(self) -> None:
        llama = self._family_module()   # the family's model module
        from kubeflow_tpu.runtime.compile_cache import ensure_compile_cache
        from kubeflow_tpu.serving.llm import LLMEngine

        # the cold-start lever beyond in-process warmup: a restarted
        # predictor reloads its program menu from the persistent compile
        # cache instead of recompiling (placed from outside — see
        # runtime/compile_cache.py)
        ensure_compile_cache()
        mesh = None
        if self._mesh:
            # tensor-parallel predictor: config.mesh {tensor: N, ...}
            from kubeflow_tpu.parallel import MeshConfig
            from kubeflow_tpu.parallel.mesh import make_mesh

            mesh = make_mesh(MeshConfig(**self._mesh))
        if (self._checkpoint and hasattr(llama, "is_hf_checkpoint")
                and llama.is_hf_checkpoint(self._checkpoint)):
            # HuggingFace-format dir (config.json + safetensors): weights,
            # architecture AND tokenizer come from one storageUri — the
            # huggingfaceserver slot (⊘ kserve python/huggingfaceserver).
            # The mesh goes INTO load_hf so an 8B checkpoint lands directly
            # sharded — materializing it whole first would OOM the chip the
            # sharding exists to relieve.
            cfg = llama.config_from_hf(self._checkpoint,
                                       **self._cfg_overrides)
            params, cfg = llama.load_hf(self._checkpoint, cfg, mesh=mesh)
            import os

            from kubeflow_tpu.serving.tokenizer import (ByteTokenizer,
                                                        load_tokenizer)

            if isinstance(self.tokenizer, ByteTokenizer) and os.path.exists(
                    os.path.join(self._checkpoint, "tokenizer.json")):
                self.tokenizer = load_tokenizer(self._checkpoint)
            if self._eos_id is None:
                self._eos_id = getattr(self.tokenizer, "eos_id", None)
        else:
            cfg = getattr(llama, self._family.config_cls)(
                **self._cfg_overrides)
            params = self._load_params(cfg)
        engine_kw = dict(n_slots=self._n_slots,
                         max_len=self._max_len,
                         buckets=self._buckets, eos_id=self._eos_id,
                         mesh=mesh,
                         decode_chunk=self._decode_chunk,
                         prefix_cache=self._prefix_cache,
                         max_prefixes=self._max_prefixes,
                         prefix_cache_blocks=self._prefix_cache_blocks,
                         quantize=self._quantize,
                         kv_quantize=self._kv_quantize,
                         speculative=self._speculative,
                         spec_ngram=self._spec_ngram,
                         spec_adaptive=self._spec_adaptive,
                         adapters=self._load_adapters(cfg),
                         logprobs_topk=self._logprobs_topk,
                         sample_k_max=self._sample_k_max,
                         pipeline_decode=self._pipeline_decode,
                         prefill_wave_max=self._prefill_wave_max,
                         warm_chain=self._warm_chain,
                         family=llama)
        # read, never pop: a second load() on this instance (unload →
        # reload is a legal Model lifecycle) must see the same config
        rewarm = bool(self._sup_cfg.get("rewarm", True))
        warmed: list[bool] = []

        def engine_factory():
            # the only sanctioned engine construction site on the
            # serving dataplane (scripts/check_dataplane.py enforces
            # this): engines are born inside a supervisor factory, so a
            # crash always has a recovery story. The first build always
            # warms (no live request waits on XLA at load); restarts
            # rewarm per config.supervisor.rewarm. config.parallel with
            # stage > 1 builds the tp×pp stage-sharded engine instead —
            # same supervision, journaling, and replay story.
            if self._pp > 1:
                from kubeflow_tpu.serving.multichip import \
                    StageShardedEngine

                # config.parallel.stage_schedule (ISSUE 20): "sync" |
                # "overlapped" wavefront dispatch; None is sync
                eng = StageShardedEngine(
                    params, cfg, stage=self._pp, tensor=self._tp,
                    stage_schedule=self._parallel.get("stage_schedule"),
                    **engine_kw)
            elif self._kv_layout == "paged":
                from kubeflow_tpu.serving.paged import PagedLLMEngine

                eng = PagedLLMEngine(params, cfg,
                                     pool_blocks=self._pool_blocks,
                                     **engine_kw)
            else:
                eng = LLMEngine(params, cfg, **engine_kw)
            if rewarm or not warmed:
                eng.warmup()
                warmed.append(True)
            return eng

        if self._disaggregated:
            from kubeflow_tpu.serving.agent import EngineSupervisor
            from kubeflow_tpu.serving.disagg import DisaggregatedEngine
            from kubeflow_tpu.serving.llm import DecodeEngine, PrefillEngine

            dg = self._disagg_cfg
            pre_kw = dict(engine_kw, prefix_cache=True)
            if dg.get("prefill_slots"):
                pre_kw["n_slots"] = int(dg["prefill_slots"])
            dec_kw = dict(engine_kw, prefix_cache=True)
            warmed_roles: dict[str, bool] = {}

            def prefill_engine_factory():
                # role engines are born inside supervisor factories too
                # (scripts/check_dataplane.py lints all three names)
                eng = PrefillEngine(params, cfg, **pre_kw)
                if rewarm or not warmed_roles.get("prefill"):
                    eng.warmup()
                    warmed_roles["prefill"] = True
                return eng

            def decode_engine_factory():
                eng = DecodeEngine(params, cfg, **dec_kw)
                if rewarm or not warmed_roles.get("decode"):
                    eng.warmup()
                    warmed_roles["decode"] = True
                return eng

            sup_kw = {k: v for k, v in self._sup_cfg.items()
                      if k != "rewarm"}
            sup_kw.setdefault("stall_timeout_s", 10.0)
            self._engine = DisaggregatedEngine(
                EngineSupervisor(prefill_engine_factory, **sup_kw),
                EngineSupervisor(decode_engine_factory, **sup_kw),
                handoff=dg.get("handoff", "zero_copy"),
                max_inflight_prefills=dg.get("max_inflight_prefills"))
        elif self._supervised:
            from kubeflow_tpu.serving.agent import EngineSupervisor

            # a conservative default stall watchdog for the HTTP path:
            # the supervisor's own 2 s default is tuned for the bench's
            # warmed miniature engines, not arbitrary deployments
            sup_kw = {k: v for k, v in self._sup_cfg.items()
                      if k != "rewarm"}
            sup_kw.setdefault("stall_timeout_s", 10.0)
            self._engine = EngineSupervisor(engine_factory, **sup_kw)
        else:
            # escape hatch for benches/tests measuring the bare engine
            self._engine = engine_factory()
        self._stop.clear()
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name=f"llm-engine-{self.name}")
        self._thread.start()
        self._mark_ready()

    def _load_adapters(self, cfg):
        """config.adapters -> engine adapter stacks: restore each named
        llama_lora checkpoint's ADAPTER subtree (the base stays the
        engine's own params — that is the whole point of multi-adapter
        serving)."""
        if not self._adapters_cfg:
            return None
        import jax

        from kubeflow_tpu.models import lora as lora_lib
        from kubeflow_tpu.serving.model import ModelError
        from kubeflow_tpu.training.checkpoint import restore_params

        out = {}
        for name, spec in self._adapters_cfg.items():
            lcfg = lora_lib.LoraLlamaConfig(
                rank=int(spec.get("rank", 8)),
                alpha=float(spec.get("alpha", 16.0)),
                targets=tuple(spec["targets"]) if "targets" in spec
                else lora_lib.LoraLlamaConfig.targets,
                llama=dict(self._cfg_overrides))
            abstract = jax.eval_shape(
                lambda lc=lcfg: lora_lib.init(jax.random.key(0), lc))
            try:
                restored = restore_params(spec["checkpoint"],
                                          {"lora": abstract["lora"]})
            except FileNotFoundError as e:
                raise ModelError(f"adapter {name!r}: {e}") from e
            out[name] = {"lora": restored["lora"], "alpha": lcfg.alpha}
        return out

    def _load_params(self, cfg):
        import jax

        llama = self._family_module()

        if self._lora is not None:
            # a llama_lora trainer checkpoint: restore {"base","lora"} and
            # merge the adapters into plain llama params
            from kubeflow_tpu.models import lora as lora_lib
            from kubeflow_tpu.serving.model import ModelError
            from kubeflow_tpu.training.checkpoint import restore_params

            if not self._checkpoint:
                raise ModelError("config.lora requires a checkpoint")
            lcfg_kw = dict(self._lora)
            # the trainer checkpoint already CONTAINS the base weights —
            # never re-read the original base here (eval_shape must stay IO
            # free)
            lcfg_kw.pop("base_checkpoint", None)
            if "targets" in lcfg_kw:
                lcfg_kw["targets"] = tuple(lcfg_kw["targets"])
            lcfg = lora_lib.LoraLlamaConfig(
                llama=dict(self._cfg_overrides), **lcfg_kw)
            abstract = jax.eval_shape(
                lambda: lora_lib.init(jax.random.key(0), lcfg))
            try:
                restored = restore_params(self._checkpoint, abstract)
            except FileNotFoundError as e:
                raise ModelError(str(e)) from e
            return lora_lib.merge(restored, lcfg,
                                  stop_base_gradient=False)
        if self._checkpoint:
            # orbax trainer checkpoint: restore the params subtree against
            # the model's abstract shapes (opt_state is not needed to
            # serve). A configured-but-empty checkpoint dir raises rather
            # than silently serving random weights.
            from kubeflow_tpu.serving.model import ModelError
            from kubeflow_tpu.training.checkpoint import restore_params

            abstract = jax.eval_shape(
                lambda: llama.init(jax.random.key(0), cfg))
            try:
                return restore_params(self._checkpoint, abstract)
            except FileNotFoundError as e:
                raise ModelError(str(e)) from e
        return llama.init(jax.random.key(self._seed), cfg)

    def _loop(self) -> None:
        """The engine thread. It owns the engine's phase clock for as
        long as it runs (`hold_open`): what is not inside `step()` is
        `sched` (the sweep) or `idle` (the wait), so every instant of
        this thread has a phase. A supervisor hands out its live
        engine's clock, anew after a restart; a disaggregated pair has
        none here (each role's clock runs inside its own steps)."""
        def enter(phase: str, hold: bool = True) -> None:
            clock = getattr(self._engine, "phase_clock", None)
            if clock is not None:
                clock.hold_open = hold
                clock.enter(phase)
                clock.leave()    # stops the clock once it is not held

        try:
            while not self._stop.is_set():
                progressed = self._engine.step()
                enter("sched")
                self._sweep_abandoned()
                if not progressed:
                    # idle: sleep until a submit wakes us
                    enter("idle")
                    self._wake.wait(timeout=0.02)
                    self._wake.clear()
                    enter("sched")
        except BaseException as e:  # surface to waiting predict() calls
            self._loop_error = e
            raise
        finally:
            enter("sched", hold=False)

    def _sweep_abandoned(self) -> None:
        for rid in list(self._abandoned):
            if self._engine.is_done(rid):
                self._engine.release(rid)
                self._abandoned.discard(rid)

    def unload(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5)
        if self._engine is not None:
            try:
                self._engine.close()   # frees device buffers / journal
            except Exception:
                pass
        super().unload()

    @property
    def supervisor(self):
        """The EngineSupervisor under this model (None on the
        supervised=False escape hatch) — the chaos harness arms fault
        scripts here, and healthz reads its accounting. Under
        disaggregated serving this is the DECODE role's supervisor (the
        replica's identity); the prefill role rides
        `prefill_supervisor`."""
        from kubeflow_tpu.serving.agent import EngineSupervisor
        from kubeflow_tpu.serving.disagg import DisaggregatedEngine

        if isinstance(self._engine, DisaggregatedEngine):
            return self._engine.decode
        return (self._engine
                if isinstance(self._engine, EngineSupervisor) else None)

    @property
    def prefill_supervisor(self):
        """The prefill role's EngineSupervisor (disaggregated serving
        only; None otherwise) — the prefill-crash chaos drill arms fault
        scripts here."""
        from kubeflow_tpu.serving.disagg import DisaggregatedEngine

        return (self._engine.prefill
                if isinstance(self._engine, DisaggregatedEngine)
                else None)

    # -- inference -----------------------------------------------------------

    def predict(self, payload: Any) -> Any:
        if isinstance(payload, list):
            return [{"output_tokens": r["token_ids"]}
                    for r in self._submit_wait_all(payload)]
        return {"output_tokens": self._wait(self._submit(payload))}

    def _submit_wait_all(self, payloads: list) -> list[dict[str, Any]]:
        """Burst primitive shared by predict() and complete_many(): ALL
        requests submit before any wait, so they share prefill waves and
        decode steps instead of serializing. On any failure, everything
        not yet drained is cancelled (frees its decode slot at the next
        chunk boundary) and abandoned (the engine loop releases it)."""
        rids: list[int] = []
        out: list[dict[str, Any]] = []
        try:
            for p in payloads:
                rids.append(self._submit(p))
            for rid in rids:
                out.append(self._wait(rid, full=True))
        except BaseException:
            # a failed _wait abandons its own rid too; cancelling it again
            # is a no-op and re-adding to the set is harmless
            for rid in rids[len(out):]:
                self._engine.cancel(rid)
                self._abandoned.add(rid)
            raise
        return out

    def _encode_stops(self, stop: Any) -> list[list[int]]:
        """OpenAI `stop` (a string, a list of strings, or token-id lists)
        → engine stop sequences. Strings are tokenizer-encoded; for a
        byte/char tokenizer this is exact, for BPE a stop string spanning
        merge boundaries may not match token-aligned output (documented —
        the buffered path additionally truncates decoded TEXT)."""
        from kubeflow_tpu.serving.protocol import ProtocolError

        if stop is None:
            return []
        if isinstance(stop, str):
            stop = [stop]
        if not isinstance(stop, list):
            raise ProtocolError("stop must be a string or a list")
        out: list[list[int]] = []
        for s in stop:
            if isinstance(s, str):
                ids = self.tokenizer.encode(s)
                if ids:
                    out.append(list(ids))
            elif isinstance(s, list):
                out.append([int(t) for t in s])
            else:
                raise ProtocolError(
                    "stop entries must be strings or id lists")
        # client-controllable input: the engine's own bounds raise bare
        # ValueErrors that the HTTP layer deliberately maps to 500;
        # surface every violation as a 400 here instead
        if len(out) > 8:
            raise ProtocolError("at most 8 stop sequences per request")
        for seq in out:
            if not 1 <= len(seq) <= 64:
                raise ProtocolError(
                    "each stop sequence must encode to 1..64 tokens")
        return out

    def _submit(self, payload: Any) -> int:
        if not isinstance(payload, dict) or "prompt_tokens" not in payload:
            raise ValueError(
                "llama runtime expects {'prompt_tokens': [...], "
                "'max_new_tokens': N}")
        prompt = [int(t) for t in payload["prompt_tokens"]]
        max_new = int(payload.get("max_new_tokens", 32))
        temperature = float(payload.get("temperature", 0.0))
        adapter = payload.get("adapter")
        # engine-enforced deadline: even an abandoned/never-drained request
        # frees its decode slot once its wall budget passes. The implicit
        # backstop sits ABOVE timeout_s so the waiter's TimeoutError (the
        # client-visible contract) always fires first — a request must not
        # nondeterministically come back 200/"cancelled" instead
        deadline = float(payload.get("deadline_s")
                         or (self._timeout_s + 10.0))
        seed = payload.get("seed")
        # trace id: taken from the payload (the HTTP layer maps the
        # X-Trace-Id header here; the router minted it upstream) or
        # minted NOW — submit is the edge for direct predict()/gRPC
        # callers. Whether spans actually record is the sampler's call.
        from kubeflow_tpu.obs.trace import new_trace_id

        trace = str(payload.get("trace") or new_trace_id())
        rid = self._engine.submit(
            prompt, max_new, temperature, adapter=adapter,
            top_k=int(payload.get("top_k", 0)),
            top_p=float(payload.get("top_p", 1.0)),
            presence_penalty=float(payload.get("presence_penalty", 0.0)),
            frequency_penalty=float(payload.get("frequency_penalty", 0.0)),
            seed=None if seed is None else int(seed),
            stop=self._encode_stops(payload.get("stop")),
            deadline_s=deadline,
            tenant=payload.get("tenant"),
            trace=trace)
        self._wake.set()
        return rid

    def _check_alive(self, deadline: float) -> None:
        """One liveness/deadline gate for every waiter (buffered + stream)."""
        if (self._stop.is_set() or self._thread is None
                or not self._thread.is_alive()):
            raise RuntimeError(
                f"llm engine loop is not running ({self._loop_error!r})")
        if time.monotonic() >= deadline:
            raise TimeoutError(
                f"generation timed out after {self._timeout_s}s")

    def stream(self, payload: Any, on_finish=None, info: dict | None = None):
        """(token_id, logprob) stream for the SSE-completions backend.
        Submits EAGERLY (not a generator itself) so unservable requests —
        PromptTooLong, QueueFull — raise before the caller commits an
        HTTP status; returns the generator that drains the engine.
        `on_finish(reason)` fires before release with the OpenAI
        finish_reason ("stop" | "length" | "cancelled"). `info`, when
        given, is filled at finish time with per-request accounting the
        final SSE usage chunk carries (currently `cached_tokens` — KV
        tokens the prefix cache reused).

        With stop sequences, the last max(len(stop))-many tokens are held
        back until the request finishes: a stop match truncates the
        result, and held-back tokens are the only ones a match can
        remove — so the stream never emits text the buffered path would
        have trimmed."""
        stops = self._encode_stops(payload.get("stop"))
        if stops:   # encode ONCE; token-id lists pass through _submit's
            payload = dict(payload, stop=stops)   # _encode_stops unchanged
        rid = self._submit(payload)
        hold = max((len(s) for s in stops), default=0)
        return self._stream_from(rid, on_finish, hold, info)

    def _timing_fields(self, rid: int) -> dict[str, Any]:
        """{"timing": the request's phase split for the usage object,
        "submit_s": its submit instant (time.monotonic)}; read BEFORE
        release. Missing phases report as None — the engine fills them
        as the boundaries land. `engine` is what the engine thread did
        over the decode_ms window. `submit_s` is not for the usage
        object: the HTTP layer times its own two spans against it
        (pre_submit_ms, first_write_lag_ms)."""
        try:
            tm = self._engine.request_timing(rid)
        except Exception:
            return {"timing": {}}
        return {"timing": {k: tm.get(k) for k in
                           ("queue_wait_ms", "prefill_ms", "handoff_ms",
                            "decode_ms", "engine", "counters")
                           if k not in ("handoff_ms", "counters")
                           or k in tm},
                "submit_s": tm.get("submit_s")}

    def _slo_record(self, rid: int, reason: str) -> None:
        """Feed one finished request into the burn tracker (read BEFORE
        release, like _timing_fields). Never raises — SLO accounting must
        not take down the serving path."""
        try:
            tm = self._engine.request_timing(rid)
        except Exception:
            return
        sub = tm.get("submit_s")
        first = tm.get("first_token_s")
        fin = tm.get("finish_s")
        n = tm.get("n_tokens") or 0
        ttft = ((first - sub) * 1e3
                if sub is not None and first is not None else None)
        tpot = ((fin - first) / (n - 1) * 1e3
                if first is not None and fin is not None and n >= 2
                else None)
        self.slo_tracker.record(tm.get("tenant"), ttft, tpot,
                                completed=reason in ("stop", "length"))

    def _cached_tokens(self, rid: int) -> int | None:
        """None when the engine runs no prefix cache (the usage object
        then omits cached_tokens entirely); 0 on a cache-on miss."""
        eng = self._engine
        if getattr(eng, "kvcache", None) is None \
                and not getattr(eng, "prefix_cache_enabled", False):
            return None
        fn = getattr(eng, "cached_tokens", None)
        return int(fn(rid)) if fn is not None else None

    def _stream_from(self, rid: int, on_finish=None, hold: int = 0,
                     info: dict | None = None):
        deadline = time.monotonic() + self._timeout_s
        sent = 0
        last_emit = time.monotonic()
        # usage_timing: the longest a token waited from its append on the
        # engine thread to this thread picking it up. Set against the
        # append's own stamp while this thread keeps up (everything
        # unsent is of the newest append), else against this thread's
        # previous look: a stream that slept through several chunks must
        # not read the newest one's age
        last_append = (getattr(self._engine, "last_append", None)
                       if self._usage_timing else None)
        lag_max = 0.0
        looked = last_emit
        try:
            while True:
                now = time.monotonic()
                done = self._engine.is_done(rid)   # BEFORE the drain: a
                # token landing between drain and check is caught next loop
                toks = self._engine.partial_result(rid)
                lps = self._engine.partial_logprobs(rid)
                limit = len(toks) if done else max(0, len(toks) - hold)
                if not done:
                    # the engine thread appends token-then-logprob; a
                    # snapshot between the two would otherwise emit a
                    # fabricated 0.0 — hold that token one poll instead
                    limit = min(limit, len(lps))
                if last_append is not None:
                    stamp = last_append(rid) if sent < limit else None
                    if stamp is not None:
                        lag_max = max(lag_max, now - (
                            stamp[1] if sent >= stamp[0] else looked))
                    looked = now
                while sent < limit:
                    yield toks[sent], (lps[sent] if sent < len(lps)
                                       else 0.0)
                    sent += 1
                    last_emit = time.monotonic()
                if done:
                    break
                if time.monotonic() - last_emit >= self._sse_keepalive_s:
                    # silence — typically a crash-restart window (backoff
                    # + rewarm) with the journal holding this stream: a
                    # (None, None) sentinel tells the HTTP layer to write
                    # an SSE keepalive comment so the client connection
                    # survives until token emission resumes, and gives it
                    # a beat to probe for client disconnect
                    yield None, None
                    last_emit = time.monotonic()
                self._check_alive(deadline)
                time.sleep(0.001)
        except BaseException:
            # a dropped SSE client (GeneratorExit via close()), a timeout,
            # or a dead loop: CANCEL so the decode slot frees at the next
            # chunk boundary instead of burning to max_new_tokens
            self._engine.cancel(rid)
            self._abandoned.add(rid)
            raise
        reason = self._engine.finish_reason(rid)
        if reason == "cancelled" and getattr(self._engine, "failed", False):
            # supervisor exhausted its restart budget mid-stream: the
            # client must see a TERMINAL error event, not a silent
            # "cancelled" that reads like its own disconnect (and never a
            # hang). The raise reaches _stream_completion's generic
            # error-chunk path; the abandoned sweep releases the rid.
            self._abandoned.add(rid)
            raise RuntimeError(
                "backend permanently failed (supervisor restart budget "
                "exhausted) after "
                f"{len(self._engine.partial_result(rid))} tokens")
        if info is not None:
            cached = self._cached_tokens(rid)
            if cached is not None:
                info["cached_tokens"] = cached
            if self._usage_timing:
                info.update(self._timing_fields(rid))
                if last_append is not None:
                    info["write_lag_max_ms"] = round(lag_max * 1e3, 3)
        if on_finish is not None:
            on_finish(reason)
        self._slo_record(rid, reason)
        self._engine.release(rid)

    def complete(self, payload: Any) -> dict[str, Any]:
        """Buffered generation: {"token_ids", "finish_reason",
        "logprobs" (per-token raw-model logprobs) and, when the engine is
        built with logprobs_topk > 0, "top_logprobs"}."""
        rid = self._submit(payload)
        return self._wait(rid, full=True)

    def complete_many(self, payloads: list) -> list[dict[str, Any]]:
        """Buffered generation for a burst (the OpenAI n/best_of
        fan-out); see _submit_wait_all."""
        return self._submit_wait_all(payloads)

    def _wait(self, rid: int, full: bool = False):
        deadline = time.monotonic() + self._timeout_s
        try:
            while not self._engine.is_done(rid):
                self._check_alive(deadline)
                time.sleep(0.001)
        except BaseException:
            # free the slot promptly (deadline/error): see _stream_from
            self._engine.cancel(rid)
            self._abandoned.add(rid)  # engine thread releases it when done
            raise
        out = self._engine.result(rid)
        reason = self._engine.finish_reason(rid)
        result = {"token_ids": out, "finish_reason": reason,
                  "logprobs": self._engine.result_logprobs(rid)}
        cached = self._cached_tokens(rid)
        if cached is not None:
            # prompt tokens whose KV the prefix cache reused (0 on a
            # miss); absent entirely when the engine runs no cache, so
            # cache-off deployments keep their exact usage shape
            result["cached_tokens"] = cached
        if self._usage_timing:
            # the phase split rides the usage object only when the
            # operator turned it on (the r10 cached_tokens precedent:
            # the default usage shape stays byte-unchanged)
            result.update(self._timing_fields(rid))
        if self._logprobs_topk:
            result["top_logprobs"] = self._engine.result_top_logprobs(rid)
        self._slo_record(rid, reason)
        self._engine.release(rid)  # long-lived server: drop request state
        return result if full else out

    def metrics(self) -> dict[str, Any]:
        return self._engine.metrics() if self._engine else {}


@serving_runtime("llama")
def _llama_runtime(name: str, uri: str | None = None,
                   **config: Any) -> Model:
    return LLMModel(name, uri, **config)


@serving_runtime("laguna")
def _laguna_runtime(name: str, uri: str | None = None,
                    **config: Any) -> Model:
    return LLMModel(name, uri, **dict(config, family="laguna"))


@serving_runtime("pangu_ultra_moe")
def _pangu_ultra_moe_runtime(name: str, uri: str | None = None,
                             **config: Any) -> Model:
    return LLMModel(name, uri, **dict(config, family="pangu_ultra_moe"))


@serving_runtime("nemotron_h")
def _nemotron_h_runtime(name: str, uri: str | None = None,
                        **config: Any) -> Model:
    return LLMModel(name, uri, **dict(config, family="nemotron_h"))
