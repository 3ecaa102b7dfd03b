"""Expert routing: of the experts a sparse layer holds, the share a decode
step touched (each one's three matrices are read for its one or two rows),
mean over the run's layer-steps, in percent: `moe_expert_visits` over
num_experts x the layer-steps counted. Lower is better: it is most of a
decode step's bytes."""

from metrics._moe_serve import touched_per_decode_call


def read(run):
    touched = touched_per_decode_call(run)
    if touched is None:
        return None
    return 100.0 * touched / run["config"]["num_experts"]
