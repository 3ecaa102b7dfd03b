"""KV manager: the share of a request's decode window in which the device was
empty while the engine thread banked prompts' blocks in the prefix cache
(`usage.engine.device_empty_by_phase_ms.prefix_bank` over
`usage.decode_ms`), median over the requests."""

from metrics._host import device_empty_share


def read(run):
    return device_empty_share(run, "prefix_bank")
