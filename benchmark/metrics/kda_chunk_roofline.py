"""Kernels: the chunked delta rule's forward kernels' (ops/kda.py: the sums
inside a chunk, and the chunks in order) share of their roofline over the
traced window, in percent. Least time: opcount/kda_chunk.py's operations and
bytes of one forward, for every layer-step: the runs of the kernel that walks
the chunks, over the runs a layer-step makes (two where the configuration
rematerialises its layers, else one). The time is that of every run of both
kernels, the second forward under remat included. The backward is XLA
operations the trace cannot tell apart: it is in neither."""

from opcount import kda_chunk as kc


def read(run):
    trace = run.get("trace")
    remat = run["config"]["system"]["model_overrides"].get("remat", True)
    took = least = 0.0
    for name, seconds, calls in (trace or {}).get("ops", []):
        walk = kc.STATE.match(name)
        if not (walk or kc.INTRA.match(name)):
            continue
        took += seconds
        if walk:
            heads, s, dk = map(int, walk.groups()[:3])
            ops, nbytes = kc.forward_cost(heads, s, dk, int(walk.groups()[8]))
            least += (calls / (2 if remat else 1)) * max(
                ops / run["peaks"]["bf16_flops_per_s"],
                nbytes / run["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / took if took and least else None
