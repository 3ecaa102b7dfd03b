"""`http_open_loop_family` for a served family with a LATENT cache and an
absorbed decode path (`reference/pangu_ultra_moe.py`'s family): the load,
the InferenceService, `correct` and the counters are that driver's own,
imported unchanged. What this adds are the faults that exist only in the
program's absorbed decode, planted under it by `BENCH_FAMILY_FAULT=<name>`
(`prove_serve_family.py --program-faults`, the tests; a benchmark run never
sets it), beside the family driver's own:

  - `decode_drops_rope`: the decode attention ignores the 64 rotary
    columns (the absorbed query's rotary part is zero);
  - `decode_query_at_zero`: a decode step's query is rotated at position 0
    and not at its own.

The prefill path is not touched by either, so only the tokens after a
prompt's first are wrong."""

from __future__ import annotations

from drivers import http_open_loop_family as family
from drivers.http_open_loop_family import child, parent  # noqa: F401


def _plant_decode_drops_rope(module) -> None:
    sound = module.latent_decode

    def drops_rope(cfg, q, *a, **kw):
        return sound(cfg, q.at[..., cfg.kv_lora_rank:].set(0), *a, **kw)
    module.latent_decode = drops_rope


def _plant_decode_query_at_zero(module) -> None:
    sound = module._queries

    def at_zero(cfg, p, i, n, positions):
        if n.shape[1] == 1:          # a decode step: one row a slot
            positions = positions * 0
        return sound(cfg, p, i, n, positions)
    module._queries = at_zero


family.PROGRAM_FAULTS.update(
    decode_drops_rope=_plant_decode_drops_rope,
    decode_query_at_zero=_plant_decode_query_at_zero)
