"""`http_open_loop_family` for a served family with a RECURRENT state per
slot beside its attention rows (`reference/nemotron_h.py`'s family): the
load, the InferenceService and the counters are that driver's own,
imported unchanged.

`correct` compares that driver's numbers, with one change to what the
off-best share counts: a served token is off the best only where its
reference logit lies more than the mix's `off_best_margin` under the
reference's best. One rounding can flip the router's 22nd choice of 512
and move a logit by up to ~1.5 whatever the precision (the widest gap reads
alike in bfloat16 and in float8), so the share of tokens off the best by
any amount counts router flips and hardly tells one precision from the
next; lower precision moves many more tokens by a few tenths.
`served_logit_gap_max` still reads the widest gap. Each request's gaps at
its served positions come back in `per_request_gaps`, from which another
margin's share can be read again.

The faults that exist only in the program's state handling are planted
under it by `BENCH_FAMILY_FAULT=<name>` (`prove_serve_family.py
--program-faults`, the tests; a benchmark run never sets it), beside the
family driver's own:

  - `pad_advances_state`: the scan of a prompt runs to the bucket's end,
    so the pad rows after the prompt feed and decay the state the slot
    keeps;
  - `state_not_reset`: a prompt's state is added to what the slot held
    before (a previous request's state), not written in its place.

A prompt's own logits are not touched by either (the scan is causal and
the first write comes after them), so only the tokens after a prompt's
first are wrong."""

from __future__ import annotations

import functools

from drivers import http_open_loop_family as family
from drivers.http_open_loop_family import parent  # noqa: F401


def served_gaps(cfg: dict, seed: int, samples: list[dict],
                lower: str | None = None, fault: str | None = None,
                pad_to: int = 128, margin: float = 0.0) -> dict:
    """`http_open_loop_family.served_gaps` (which the accepted cells keep
    as it is), a served token counted off the best only where its gap
    exceeds `margin`."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    ref = family.reference_of(cfg)
    longest = max(len(s["prompt"]) + len(s["tokens"]) for s in samples)
    t = -(-longest // pad_to) * pad_to     # one shape; causal: pad unseen
    toks = jnp.asarray([(s["prompt"] + s["tokens"]
                         + [0] * t)[:t] for s in samples], jnp.int32)
    hidden = ref.hidden(seed, toks, cfg)
    stood_in = None
    if lower is not None or fault is not None:
        stood_in = ref.hidden(seed, toks, cfg, lower=lower, fault=fault)

    @jax.jit
    def gaps_of(ends, h, h_in, toks):
        lg = ref.head(ends, h, cfg)                          # [T, V]
        pos = jnp.arange(toks.shape[0])
        judged = (toks[jnp.minimum(pos + 1, toks.shape[0] - 1)]
                  if h_in is None
                  else jnp.argmax(ref.head(ends, h_in, cfg, lower), -1))
        return lg.max(-1) - jnp.take_along_axis(lg, judged[:, None], -1)[:, 0]

    ends = ref.ends(seed, cfg)
    gaps = []
    for i, s in enumerate(samples):
        gap = gaps_of(ends, hidden[i],
                      None if stood_in is None else stood_in[i], toks[i])
        first = len(s["prompt"]) - 1
        gaps.append(np.asarray(gap[first:first + len(s["tokens"])],
                               np.float64))
    off = [int(np.sum(g > margin)) for g in gaps]
    total = sum(len(g) for g in gaps)
    return {"widest_gap": max((float(g.max(initial=0.0)) for g in gaps),
                              default=0.0),
            "per_request": [float(g.max(initial=0.0)) for g in gaps],
            "per_request_off_best": off,
            "per_request_tokens": [len(g) for g in gaps],
            "per_request_gaps": [[round(float(v), 5) for v in g]
                                 for g in gaps],
            "off_best_margin": margin, "tokens_judged": total,
            "tokens_agreeing": total - sum(off),
            "off_best_share": 100.0 * sum(off) / max(total, 1)}


def child(ctx, fault=None) -> None:
    family.served_gaps = functools.partial(
        served_gaps, margin=float(ctx.cell.traffic["off_best_margin"]))
    family.child(ctx, fault)


def _plant_pad_advances_state(module) -> None:
    sound = module.ssd_scan

    def to_the_end(x, dt, a, bm, cm, h0, lengths):
        return sound(x, dt, a, bm, cm, h0, lengths * 0 + x.shape[1])
    module.ssd_scan = to_the_end


def _plant_state_not_reset(module) -> None:
    sound = module.cache_write

    def added(cache, slot, start, count, ks, vs, **kw):
        out = sound(cache, slot, start, count, ks, vs, **kw)
        if start == 0:      # a prompt's first write: the slot's old state
            out["ssm"] = out["ssm"].at[:, slot].add(cache["ssm"][:, slot])
        return out
    module.cache_write = added


family.PROGRAM_FAULTS.update(
    pad_advances_state=_plant_pad_advances_state,
    state_not_reset=_plant_state_not_reset)
