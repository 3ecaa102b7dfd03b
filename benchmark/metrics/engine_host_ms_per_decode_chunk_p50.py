"""Engine step: the host's own work per decode chunk: per request the
engine thread's `sched` + `decode_plan` + `decode_dispatch` + `replay`
milliseconds over the decode programs it dispatched in the request's
decode window; median over the requests."""

from lib import stats
from metrics._engine import engine_usages, phase_count, phase_ms


def read(run):
    return stats.percentile(
        [phase_ms(e, "sched", "decode_plan", "decode_dispatch", "replay")
         / phase_count(e, "decode_dispatch")
         for _, e in engine_usages(run)
         if phase_count(e, "decode_dispatch")], 50)
