"""Kernels: of the KV blocks the decode dispatches' attention grids covered
over a request's decode window (16 slots x span / block a dispatch), the
share that the slots' lengths let the flash-decode kernel copy
(`usage.engine.kv_blocks` = [fetched, spanned], counted by the engine at
each dispatch: kubeflow_tpu/obs/trace.py, `PhaseClock.note_kv_blocks`), in
percent, median over the requests. Lower is better: what is not fetched
costs no bytes. A program whose kernel fetches every block of the span
(an older commit) sends no such key: None."""

from lib import stats
from metrics._engine import engine_usages


def read(run):
    return stats.percentile(
        [100.0 * e["kv_blocks"][0] / e["kv_blocks"][1]
         for _, e in engine_usages(run)
         if e.get("kv_blocks") and e["kv_blocks"][1]], 50)
