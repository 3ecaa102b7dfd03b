"""Scheduler: the share of a request's decode window in which the device was
empty while the engine thread asked the scheduler, packed a prefill wave or
planned a decode chunk (`usage.engine.device_empty_by_phase_ms` of `sched` +
`prefill_pack` + `decode_plan` over `usage.decode_ms`), median."""

from metrics._host import device_empty_share


def read(run):
    return device_empty_share(run, "sched", "prefill_pack", "decode_plan")
