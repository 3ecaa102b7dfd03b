"""Test fixtures: run everything on a virtual 8-device CPU mesh.

This is the reference's "distributed-without-a-cluster" trick (SURVEY.md §4.4)
adapted to JAX: instead of asserting on pods an operator *would* create, we run
the real sharded programs on 8 virtual CPU devices so multi-chip semantics
(collectives, shardings, gang sizes) are exercised for real — just not fast.

Env vars must be set before jax initializes its backends, hence the top of
conftest. Tests marked `tpu` are skipped here; the chip is reached through
chip_smoke.py and benchmark/run.py.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")
# silence XLA:CPU AOT-cache feature-bookkeeping logs (one E-line per
# persistent-cache load; the pseudo-features ±prefer-no-* never match the
# detected host string even on the same machine)
os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "3")

import jax  # noqa: E402

# No persistent compilation cache here: on this jaxlib/XLA:CPU combination
# it is NOT numerics-safe — in a process that mixes freshly-compiled and
# deserialized executables (any run after an HLO-changing edit, or a cold
# cache being populated), engine programs return WRONG tokens: seeded
# sampling loses engine-independence and penalized greedy diverges from
# the host reference (reproduced r6 on an unmodified tree: cold-cache run
# fails 4 sampling tests, the warm rerun passes all 14). A pre-warmed loop
# where every process is fully warm can opt in from outside with
# JAX_COMPILATION_CACHE_DIR (runtime/compile_cache.py leaves it alone).

import signal  # noqa: E402
import threading  # noqa: E402
import time  # noqa: E402

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line("markers", "tpu: requires real TPU hardware")
    config.addinivalue_line("markers", "slow: long-running e2e test")


@pytest.fixture(scope="session")
def devices8():
    devs = jax.devices()
    if len(devs) < 8:
        pytest.skip("needs 8 virtual devices")
    return devs[:8]


# -- subprocess containment (ISSUE 10 satellite) ------------------------------
# Tests that spawn real subprocesses (test_multiprocess_*, the chaos
# suite) get a safety net: any child process that appears during the
# test and survives teardown — or outlives the watchdog timeout — is
# killed along with its whole process GROUP. A hung fault-injection
# child can therefore never starve the tier-1 wall clock: the group
# kill fires from a daemon timer even while the test body is blocked
# in a wait().

def _child_pids() -> set[int]:
    """Direct children of this process (via /proc; Linux-only, which is
    the only platform the tier-1 lane runs on)."""
    me = os.getpid()
    kids: set[int] = set()
    try:
        entries = os.listdir("/proc")
    except OSError:
        return kids
    for d in entries:
        if not d.isdigit():
            continue
        try:
            with open(f"/proc/{d}/stat", "rb") as f:
                # field 4 (after the parenthesized comm, which may
                # itself contain spaces) is ppid
                ppid = int(f.read().split(b") ", 1)[1].split()[1])
        except (OSError, IndexError, ValueError):
            continue
        if ppid == me:
            kids.add(int(d))
    return kids


def _kill_group(pid: int, sig: int) -> None:
    """Kill pid's process group — but NEVER our own (a child spawned
    without start_new_session shares pytest's group; killpg there would
    take the whole test session down)."""
    try:
        pgid = os.getpgid(pid)
    except OSError:
        return
    try:
        if pgid != os.getpgid(0):
            os.killpg(pgid, sig)
        else:
            os.kill(pid, sig)
    except OSError:
        pass


@pytest.fixture
def procgroup_guard():
    """Reap surviving child process groups on teardown, and after a hard
    watchdog timeout even if the test body is still blocked. Use on any
    test that spawns subprocesses."""
    before = _child_pids()

    def reap():
        new = _child_pids() - before
        if not new:
            return
        for pid in new:
            _kill_group(pid, signal.SIGTERM)
        deadline = time.monotonic() + 2.0
        while time.monotonic() < deadline and _child_pids() - before:
            time.sleep(0.1)
        for pid in _child_pids() - before:
            _kill_group(pid, signal.SIGKILL)

    watchdog = threading.Timer(240.0, reap)
    watchdog.daemon = True
    watchdog.start()
    try:
        yield
    finally:
        watchdog.cancel()
        reap()


