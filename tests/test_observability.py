"""Metrics registry (§5.5), step profiler (§5.1), and KfDef component
gating (§2.1 kfctl analog)."""

from __future__ import annotations

import os
import urllib.request

import pytest

from kubeflow_tpu.control import worker_target
from kubeflow_tpu.utils.metrics import Registry


@worker_target("obs_ok")
def _ok(env, cancel):
    pass


# -- registry ----------------------------------------------------------------

def test_counter_gauge_render():
    r = Registry()
    c = r.counter("jobs_total", "jobs", ["kind"])
    c.inc(kind="TFJob")
    c.inc(2, kind="TFJob")
    g = r.gauge("depth", "queue depth")
    g.set(4)
    g.dec()
    text = r.render()
    assert '# TYPE jobs_total counter' in text
    assert 'jobs_total{kind="TFJob"} 3' in text
    assert 'depth 3' in text
    assert c.value(kind="TFJob") == 3.0


def test_histogram_buckets():
    r = Registry()
    h = r.histogram("lat", "latency", ["op"], buckets=(0.1, 1.0))
    h.observe(0.05, op="get")
    h.observe(0.5, op="get")
    h.observe(5.0, op="get")
    text = r.render()
    assert 'lat_bucket{le="0.1",op="get"} 1' in text
    assert 'lat_bucket{le="1",op="get"} 2' in text
    assert 'lat_bucket{le="+Inf",op="get"} 3' in text
    assert 'lat_count{op="get"} 3' in text
    with h.time(op="get"):
        pass
    assert 'lat_count{op="get"} 4' in r.render()


def test_label_mismatch_and_type_conflict():
    r = Registry()
    c = r.counter("x", "", ["a"])
    with pytest.raises(ValueError):
        c.inc(b="1")
    with pytest.raises(ValueError):
        r.gauge("x")
    # same name+type+labels returns the same instance
    assert r.counter("x", "", ["a"]) is c
    with pytest.raises(ValueError):  # label mismatch caught at registration
        r.counter("x", "", ["b"])


def test_full_precision_values_and_label_escaping():
    r = Registry()
    c = r.counter("big", "", ["reason"])
    c.inc(1234567, reason='bad "spec"\nline2')
    text = r.render()
    assert 'big{reason="bad \\"spec\\"\\nline2"} 1234567' in text
    g = r.gauge("frac")
    g.set(0.1)
    assert "frac 0.1" in r.render()


def test_controller_metrics_emitted_and_served():
    """Running a job bumps the kubeflow/common-analog counters, and the API
    server exposes them at /metrics in prometheus text format."""
    from kubeflow_tpu.api.platform import Platform
    from kubeflow_tpu.api.server import ApiServer
    from kubeflow_tpu.control.store import new_resource
    from kubeflow_tpu.control.conditions import is_finished
    from kubeflow_tpu.utils.metrics import JOBS_SUCCESSFUL

    before = JOBS_SUCCESSFUL.value(kind="JAXJob")
    with Platform(n_devices=8, components=("training",)) as p:
        p.apply(new_resource("JAXJob", "m1", spec={
            "replicaSpecs": {"worker": {"replicas": 1, "template": {
                "backend": "thread", "target": "obs_ok"}}}}))
        p.wait("JAXJob", "m1")
        server = ApiServer(p).start()
        try:
            with urllib.request.urlopen(server.url + "/metrics") as resp:
                assert "text/plain" in resp.headers["Content-Type"]
                text = resp.read().decode()
        finally:
            server.stop()
    assert JOBS_SUCCESSFUL.value(kind="JAXJob") == before + 1
    assert 'training_jobs_successful_total{kind="JAXJob"}' in text
    assert 'controller_reconcile_duration_seconds_bucket' in text


# -- profiler ----------------------------------------------------------------

def test_step_profiler_captures_window(tmp_path):
    from kubeflow_tpu.training import Trainer, TrainerConfig, OptimizerConfig
    from kubeflow_tpu.training import data as data_lib

    logdir = str(tmp_path / "prof")
    trainer = Trainer(TrainerConfig(
        model="mnist_cnn", batch_size=4,
        optimizer=OptimizerConfig(warmup_steps=1, total_steps=10),
        profile_dir=logdir, profile_start_step=2, profile_num_steps=2,
        log_every=100))
    trainer.metrics.echo = False
    data = data_lib.for_model("mnist_cnn", trainer.model_cfg, 4)
    trainer.train(data, 5)
    assert os.path.exists(os.path.join(logdir, "PROFILE_DONE"))
    # jax.profiler writes the tensorboard-profile plugin layout
    assert any("plugins" in root or f.endswith(".xplane.pb")
               for root, _dirs, files in os.walk(logdir) for f in (files or [""]))


def test_trace_context_manager(tmp_path):
    import jax.numpy as jnp

    from kubeflow_tpu.training.profiling import trace

    with trace(str(tmp_path / "t")) as d:
        jnp.ones((8, 8)).sum().block_until_ready()
    assert os.path.isdir(d)


# -- KfDef -------------------------------------------------------------------

def test_kfdef_validation_and_components():
    from kubeflow_tpu.api.kfdef import (components_of, default_kfdef,
                                        validate_kfdef)

    kd = default_kfdef("dep")
    assert validate_kfdef(kd) == []
    assert components_of(kd) == ("training", "hpo", "pipelines", "serving",
                                 "platform")
    kd["spec"]["applications"] = [{"name": "hpo"}]
    assert any("requires 'training'" in e for e in validate_kfdef(kd))
    kd["spec"]["applications"] = [{"name": "nope"}]
    assert any("unknown" in e for e in validate_kfdef(kd))


def test_platform_component_gating():
    from kubeflow_tpu.api.platform import Platform

    p = Platform(n_devices=2, components=("training", "serving"))
    kinds = {c.kind for c in p.cluster.controllers}
    assert "JAXJob" in kinds and "TFJob" in kinds
    assert "InferenceService" in kinds
    assert "Experiment" not in kinds and "PipelineRun" not in kinds
    assert "Notebook" not in kinds
    assert p.hpo_db is None and p.pipelines is None
    with pytest.raises(ValueError):
        Platform(n_devices=2, components=("hpo",))  # needs training


def test_cli_init_scaffold(tmp_path, capsys):
    import yaml

    from kubeflow_tpu.cli import main

    d = str(tmp_path / "deploy")
    assert main(["init", d]) == 0
    with open(os.path.join(d, "kfdef.yaml")) as f:
        kd = yaml.safe_load(f)
    assert kd["kind"] == "KfDef" and kd["metadata"]["name"] == "deploy"
    assert main(["init", d]) == 1  # refuses to clobber


# -- the serving-plane instrument set: host time's series (ISSUE 36) ----------

@pytest.mark.parametrize("var, series, kind, labels", [
    ("ENGINE_PHASE_SECONDS", "serving_engine_phase_seconds_total",
     "counter", ("engine", "phase")),
    ("ENGINE_PHASE_CPU_SECONDS", "serving_engine_phase_cpu_seconds_total",
     "counter", ("engine", "phase")),
    ("ENGINE_DEVICE_EMPTY_SECONDS",
     "serving_engine_device_empty_seconds_total", "counter",
     ("engine", "phase")),
    ("GC_PAUSE_SECONDS", "process_gc_pause_seconds_total", "counter",
     ("generation",)),
    ("GC_PAUSE_MAX_SECONDS", "process_gc_pause_max_seconds", "gauge", ()),
])
def test_host_time_series_are_declared_once_and_rendered(var, series, kind,
                                                         labels):
    """Each series of the phase clock's host-time split is declared in
    `obs/metrics.py` (where `scripts/check_observability.py` wants every
    serving-plane name), with the labels the docs give it, and one
    scrape of a clock that has run carries it with its TYPE line; what a
    clock publishes under the phase label sums, over `phase`, to the
    whole it published before the label came."""
    from kubeflow_tpu.obs import metrics as obs_metrics
    from kubeflow_tpu.obs.trace import PHASES, PhaseClock

    metric = getattr(obs_metrics, var)
    assert (metric.name, metric.kind, metric.label_names) == (
        series, kind, labels)
    # re-declaring it elsewhere with other labels is refused
    with pytest.raises(ValueError):
        getattr(obs_metrics.REGISTRY, kind)(series, "", ["other"])
    clock = PhaseClock(f"unit-{var}")
    clock.enter("decode_fetch")
    clock.fetched(outstanding=False)
    clock.enter("replay")
    clock.enter("decode_dispatch")
    clock.leave()
    clock.publish()
    text = obs_metrics.render_metrics()
    assert f"# TYPE {series} {kind}" in text
    if "phase" in labels:
        got = {p: metric.value(engine=f"unit-{var}", phase=p)
               for p in PHASES}
        want = {"ENGINE_PHASE_SECONDS": clock.ns,
                "ENGINE_PHASE_CPU_SECONDS": clock.cpu_ns,
                "ENGINE_DEVICE_EMPTY_SECONDS":
                    clock.device_empty_by_phase}[var]
        assert got == {p: want[p] / 1e9 for p in PHASES}
        assert sum(got.values()) > 0
        for p in PHASES:
            assert f'{series}{{engine="unit-{var}",phase="{p}"}}' in text
    if var == "ENGINE_DEVICE_EMPTY_SECONDS":
        assert sum(got.values()) == pytest.approx(
            clock.device_empty_ns / 1e9)
        assert got["decode_dispatch"] == 0
