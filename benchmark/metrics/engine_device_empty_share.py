"""Engine step: the share of a request's decode window in which the engine
had nothing dispatched and unfetched (`usage.engine.device_empty_ms` over
`usage.decode_ms`), median over the requests. The device is certainly idle
then, so this is a floor under the traced idle share, read without a
profiler."""

from lib import stats
from metrics._engine import engine_usages


def read(run):
    return stats.percentile(
        [100.0 * e["device_empty_ms"] / u["decode_ms"]
         for u, e in engine_usages(run)], 50)
