"""Scheduler: how much of a request's decode window the engine thread
spent on OTHER requests' prefill waves (`prefill_pack` + `prefill_dispatch`
+ `prefix_bank` + `prefill_fetch` over `usage.decode_ms`), median over the
requests."""

from lib import stats
from metrics._engine import engine_usages, phase_ms


def read(run):
    return stats.percentile(
        [100.0 * phase_ms(e, "prefill_pack", "prefill_dispatch",
                          "prefix_bank", "prefill_fetch") / u["decode_ms"]
         for u, e in engine_usages(run)], 50)
