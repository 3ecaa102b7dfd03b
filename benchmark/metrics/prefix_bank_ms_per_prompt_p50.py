"""KV manager: what banking one burst's prompts costs the engine thread:
over the requests whose decode window saw a bank, `prefix_bank`
milliseconds over its occurrences (one per prefill burst); median."""

from lib import stats
from metrics._engine import engine_usages, phase_count, phase_ms


def read(run):
    return stats.percentile(
        [phase_ms(e, "prefix_bank") / phase_count(e, "prefix_bank")
         for _, e in engine_usages(run)
         if phase_count(e, "prefix_bank")], 50)
