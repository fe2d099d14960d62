"""Build the system under test from a configuration file and put it
behind its own HTTP server, in this process.

A configuration file names its engine builder and its model-config
class by import path and maps the published keys onto the class's
fields, so a new family needs a file, not an edit here. ``chips`` > 1
hands the builder a ``tp`` mesh over that many devices.
"""

from __future__ import annotations

import asyncio
import importlib
import json
import os
import threading

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load_config(name: str) -> dict:
    with open(os.path.join(HERE, "configs", f"{name}.json")) as f:
        return json.load(f)


def load_reference(cfg: dict):
    """The configuration's plain reference module (benchmarks/references)."""
    return importlib.import_module(f"references.{cfg['reference']}")


class IdTokenizer:
    """A prompt is space-separated decimal token ids, so its length in
    tokens is exact and the ids cover the whole vocabulary."""

    eos_id = -1

    def encode(self, text: str) -> list[int]:
        return [int(t) for t in text.split()]

    def decode(self, ids) -> str:
        return " ".join(str(int(t)) for t in ids)


def model_config(cfg: dict):
    b = cfg["builder"]
    cls = getattr(importlib.import_module(b["model_module"]), b["model_class"])
    return cls(**{field: cfg[key] for field, key in b["model_keys"].items()})


def build_engine(cfg: dict, params, seed: int, chips: int):
    """The engine as the configuration file describes it."""
    import jax
    from gofr_tpu.serving.engine import EngineConfig
    b = cfg["builder"]
    builder = getattr(importlib.import_module(b["module"]), b["function"])
    engine_keys = {k: tuple(v) if isinstance(v, list) else v
                   for k, v in cfg["engine"].items()}
    kw = {}
    if chips > 1:
        from gofr_tpu.parallel import create_mesh
        kw["mesh"] = create_mesh({"tp": chips}, jax.devices()[:chips])
    return builder(params, model_config(cfg),
                   EngineConfig(seed=seed % 2 ** 31, **engine_keys), **kw)


class AppThread:
    """The app on a free port, its event loop in a thread of its own
    (the engine's loop is a further thread the app starts)."""

    def __init__(self, engine, name: str = "bench") -> None:
        from gofr_tpu.app import App
        from gofr_tpu.config import DictConfig
        self.app = App(config=DictConfig({
            "HTTP_PORT": "0", "METRICS_PORT": "0", "APP_NAME": name,
            "GOFR_TELEMETRY": "false", "LOG_LEVEL": "ERROR"}))
        self.app.serve_model("llama", engine, IdTokenizer())
        self._loop = None
        self._thread = None
        self._started = threading.Event()
        self._error = None

    def __enter__(self) -> "AppThread":
        def runner():
            self._loop = asyncio.new_event_loop()
            asyncio.set_event_loop(self._loop)

            async def main():
                try:
                    await self.app.start()
                finally:
                    self._started.set()
                await self.app._stop_event.wait()

            try:
                self._loop.run_until_complete(main())
            except Exception as exc:
                self._error = exc
                self._started.set()
            finally:
                self._loop.close()

        self._thread = threading.Thread(target=runner, name="bench-app")
        self._thread.start()
        if not self._started.wait(30):
            raise TimeoutError("the app did not start")
        if self._error is not None:
            raise self._error
        return self

    def __exit__(self, *exc) -> None:
        if self._loop is not None and self._loop.is_running():
            asyncio.run_coroutine_threadsafe(
                self.app.stop(), self._loop).result(60)
        self._thread.join(60)
        if self._thread.is_alive():
            raise RuntimeError("the app's thread did not end")

    @property
    def port(self) -> int:
        return self.app.http_server.bound_port
