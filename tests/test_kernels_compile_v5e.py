"""The KDA kernels compiled by the REAL v5e compiler at the Kimi-Linear cut,
from this CPU process: a v5e topology described, not attached (the
on-chip-measurement guide, section 2). tests/test_kernels_lower_tpu.py runs
JAX's own Pallas->Mosaic lowering, which the interpreter's parity tests do
not; this file runs Mosaic itself, which refused a slice of an iota that
both of those accepted (PR 37). Nothing runs: no result, no time.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU's library, and a worker that cannot
skips these tests instead of leaving the suite uncollected.
"""

import os

import jax
import jax.numpy as jnp
import pytest

from kubeflow_tpu.ops import kda


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:     # no libtpu, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def kimi_cut(sharding):
    """2 rows x 32 heads of 8192 positions, dk = dv = 128: rows, decay,
    beta, square ([BH, NC, 64, 64]), per-chunk rows, states, gamma."""
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                    sharding=sharding)
    return dict(rows=sds((64, 8192, 128), jnp.bfloat16),
                decay=sds((64, 8192, 128), jnp.float32),
                beta=sds((64, 8192), jnp.float32),
                square=sds((64, 128, 64, 64), jnp.float32),
                chunk_rows=sds((64, 128, 64, 128), jnp.float32),
                states=sds((64, 128, 128, 128), jnp.float32),
                gamma=sds((64, 128, 128), jnp.float32))


def kernel_call(kernel, s):
    """(function, abstract operands) of one of the four KDA kernels."""
    kw = dict(interpret=False, mm_dtype=jnp.bfloat16)
    if kernel == "intra":
        return (lambda q, k, gc: kda._intra_pallas(q, k, gc, **kw),
                (s["rows"], s["rows"], s["decay"]))
    if kernel == "state":
        return (lambda q, k, v, gc, m, b: kda._state_pallas(
                    q, k, v, gc, m, b, emit_states=True, **kw),
                (s["rows"],) * 3 + (s["decay"], s["square"], s["square"]))
    if kernel == "state_bwd":
        return (lambda *a: kda._state_bwd_pallas(*a, **kw),
                (s["rows"],) * 3 + (s["decay"], s["square"], s["square"],
                                    s["states"], s["rows"]))
    return (lambda q, k, v, gc, beta, x, *d: kda._prepare_bwd_pallas(
                q, k, v, gc, beta, x, d, **kw),
            (s["rows"],) * 3 + (s["decay"], s["beta"], s["square"])
            + (s["chunk_rows"],) * 3 + (s["square"], s["chunk_rows"],
                                        s["gamma"]))


@pytest.mark.parametrize("kernel", ["intra", "state", "state_bwd",
                                    "prepare_bwd"])
def test_kda_kernel_compiles_for_v5e(one_chip, kernel):
    fn, args = kernel_call(kernel, kimi_cut(one_chip))
    compiled = jax.jit(fn).lower(*args).compile()
    assert 'custom_call_target="tpu_custom_call"' in compiled.as_text()
