"""TPU device registry: enumeration, caching, health, metrics, and the
hung-probe timeout path."""

import time

from gofr_tpu.container.mock import new_mock_container
from gofr_tpu.device import DeviceRegistry


def test_enumerates_devices():
    reg = DeviceRegistry()
    devices = reg.devices()
    assert len(devices) >= 1  # virtual cpu mesh from conftest
    d = devices[0]
    assert {"id", "platform", "kind", "process_index"} <= set(d)
    assert reg.device_count() == len(devices)


def test_cache_ttl_avoids_reprobe():
    reg = DeviceRegistry(cache_ttl_s=60)
    reg.devices()
    probes = {"n": 0}
    original = DeviceRegistry._probe

    def counting():
        probes["n"] += 1
        return original()
    reg._probe = counting
    reg.devices()
    assert probes["n"] == 0  # served from cache
    reg.devices(refresh=True)
    assert probes["n"] == 1


def test_health_up_with_engines():
    reg = DeviceRegistry()

    class FakeEngine:
        def health_check(self):
            return {"status": "UP", "steps": 7}
    reg.register_engine("llama", FakeEngine())
    health = reg.health_check()
    assert health["status"] == "UP"
    assert health["details"]["device_count"] >= 1
    assert health["details"]["engines"]["llama"]["steps"] == 7


def test_dead_backend_times_out_and_reports_down():
    reg = DeviceRegistry(probe_timeout_s=0.2, cache_ttl_s=0)

    def hang():
        time.sleep(5)
        return []
    reg._probe = hang
    start = time.time()
    assert reg.devices() == []
    assert time.time() - start < 2.0  # bounded, no hang
    health = reg.health_check()
    assert health["status"] == "DOWN"
    assert "exceeded" in health["details"]["error"]


def test_stale_cache_degrades_instead_of_down():
    reg = DeviceRegistry(cache_ttl_s=0)
    devices = reg.devices()
    assert devices  # real probe worked

    def boom():
        raise ConnectionError("device runtime gone")
    reg._probe = boom
    still = reg.devices()
    assert still == devices  # stale cache served
    assert reg.health_check()["status"] == "DEGRADED"


def test_publish_metrics_sets_gauges():
    c = new_mock_container()
    reg = DeviceRegistry(metrics=c.metrics)
    reg.publish_metrics()
    gauge = c.metrics.get("app_tpu_device_count")
    assert gauge is not None
    # cpu devices may not expose memory_stats; the count gauge must exist
    rendered = c.metrics.render_prometheus()
    assert "app_tpu_device_count" in rendered


def test_serve_model_attaches_registry():
    from gofr_tpu.app import App
    from gofr_tpu.config.env import DictConfig
    from gofr_tpu.serving.glue import demo_llama_engine

    app = App(config=DictConfig({"HTTP_PORT": "0", "METRICS_PORT": "0"}))
    app.serve_model("llama", demo_llama_engine(), chat_path=None)
    assert type(app.container.tpu).__name__ == "DeviceRegistry"
    assert "llama" in app.container.tpu.engines
    health = app.container.health()
    assert "tpu" in health["checks"]
