"""Engine step: of the wall the engine thread spent in the phases that never
wait for the device (`sched`, `prefill_pack`, `decode_plan`, `replay`), the
share it was OFF the CPU (`usage.engine.phases` less `usage.engine.cpu_ms`),
median over the requests: the thread waiting for the interpreter or the OS."""

from metrics._host import HOST_ONLY, offcpu_share


def read(run):
    return offcpu_share(run, *HOST_ONLY)
