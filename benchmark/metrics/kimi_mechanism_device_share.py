"""Kernels: the time of the Kimi-Linear mechanisms' own kernels (the KDA
chunk kernels, latent attention's flash kernels, the routed experts' grouped
matmuls) over the device's busy time in the traced window, in percent: do
the new mechanisms do the work in this cell, or do the plain matmuls?"""

from opcount import grouped_matmul as gm
from opcount import kda_chunk as kc
from opcount import mla_attention as ma

KERNELS = (kc.INTRA.match, kc.STATE.match, ma.kernel, gm.GMM.match,
           gm.TGMM.match)


def read(run):
    trace = run.get("trace")
    if not trace or not trace.get("busy_s"):
        return None
    took = sum(seconds for name, seconds, _ in trace.get("ops", [])
               if any(k(name) for k in KERNELS))
    return 100.0 * took / trace["busy_s"] if took else None
