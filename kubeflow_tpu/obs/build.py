"""Build/runtime stamp for /healthz (ISSUE 17 satellite): the
kubeflow_tpu version plus the jax/jaxlib pair and the live device view,
so fleet tooling can detect restarts and version skew from one GET.
"""

from __future__ import annotations

from typing import Any

from kubeflow_tpu.version import __version__

_STAMP: dict[str, Any] | None = None


def runtime_stamp() -> dict[str, Any]:
    """platform/device_kind/device_count/jax/jaxlib of THIS process.
    Touches the jax backend, so callers on latency paths should prefer
    the cached ``build_stamp()``."""
    import jax

    dev = jax.devices()[0]
    try:
        import jaxlib

        jaxlib_v = getattr(jaxlib, "__version__", None)
    except Exception:
        jaxlib_v = None
    return {
        "platform": str(dev.platform),
        "device_kind": str(dev.device_kind),
        "device_count": jax.device_count(),
        "jax": jax.__version__,
        "jaxlib": jaxlib_v or jax.__version__,
    }


def build_stamp() -> dict[str, Any]:
    """The /healthz ``build`` section: version skew surface. Computed
    once per process (the device view cannot change under a fixed
    backend) and never raises — a frontend must stay healthy even if
    the accelerator runtime is broken enough to fail a device query."""
    global _STAMP
    if _STAMP is None:
        stamp: dict[str, Any] = {"kubeflow_tpu": __version__}
        try:
            stamp.update(runtime_stamp())
        except Exception as e:   # jax missing/broken: version info only
            stamp["runtime_error"] = f"{type(e).__name__}: {e}"
        _STAMP = stamp
    return dict(_STAMP)
