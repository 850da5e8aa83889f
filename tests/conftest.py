"""Test fixtures (reference analog: tests/common_fixtures.py — config reset
:58, RunDBMock :241).

Tests run on a virtual 8-device CPU mesh so distributed step functions are
unit-testable without TPUs (SURVEY.md §4 implication).
"""

import os
import sys
import tempfile

# must happen before the first jax backend init: tests run on the CPU
# backend whatever the host pins. Both the env AND jax.config are updated,
# in case jax was already imported (config read the env at jax import).
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running suites excluded from tier-1")
    config.addinivalue_line(
        "markers", "chaos: fault-injection tests (run via `make chaos`)")


_COMPILE_CACHE_DIR = None

# serving/engine suites share one persistent XLA compile cache: they
# build dozens of near-identical tiny-model engines whose compiles
# dominate their wall time. STRICTLY engine modules — enabling the
# cache session-wide segfaults the trainer path (test_checkpoint's
# preemption fit with a live device-prefetch producer thread), so
# training modules run exactly as before.
_COMPILE_CACHED_MODULES = {
    "test_serving_prefix", "test_serving_fleet", "test_serving_adapters",
    "test_fleet_elastic", "test_control_recovery",
    "test_serving_resilience", "test_llm_continuous", "test_llm_paged",
    "test_llm_engine", "test_paged_attention", "test_paged_prefill",
    "test_speculative", "test_spec_paged", "test_kv_tier",
    "test_sdar_paged",
    "test_replica_health",
    "test_observability", "test_obs_control_plane",
    "test_continuous_tuning", "test_request_forensics",
    # trainer-path exception to the engines-only rule: the elastic suite
    # compiles the SAME tiny step function at three mesh shapes per test
    # — the cache collapses that to one compile each. Safe here because
    # its fits run prefetch=0 (no live producer thread, the segfault
    # ingredient the note above names)
    "test_elastic_training",
}


@pytest.fixture(scope="module", autouse=True)
def _engine_shared_compile_cache(request, tmp_path_factory):
    """One shared persistent compile cache across the engine-heavy
    serving/LLM modules (allowlist above): every duplicate program after
    the first loads its executable from disk — bit-identical results
    (content-addressed executables), only the compile time goes away,
    which is what keeps tier-1 inside its wall budget. Disabled on
    module exit so non-engine modules are untouched."""
    global _COMPILE_CACHE_DIR

    name = request.module.__name__.rsplit(".", 1)[-1]
    if name not in _COMPILE_CACHED_MODULES:
        yield None
        return
    from mlrun_tpu.utils import compile_cache

    if _COMPILE_CACHE_DIR is None:
        _COMPILE_CACHE_DIR = str(tmp_path_factory.mktemp("xla-cache"))
    compile_cache.configure(_COMPILE_CACHE_DIR)
    yield _COMPILE_CACHE_DIR
    compile_cache.disable()


@pytest.fixture(autouse=True)
def _chaos_dark():
    """No armed fault survives a test — a leaked injection would poison
    every later test through the process-wide registry."""
    from mlrun_tpu.chaos import chaos

    chaos.clear()
    yield
    chaos.clear()


@pytest.fixture(autouse=True)
def isolated_home(monkeypatch, tmp_path):
    """Fresh MLT_HOME + fresh config + fresh run DB per test."""
    monkeypatch.setenv("MLT_HOME", str(tmp_path / "mlt-home"))
    monkeypatch.delenv("MLT_DBPATH", raising=False)

    from mlrun_tpu.config import mlconf

    mlconf.reload()

    import mlrun_tpu.db as db_mod
    from mlrun_tpu.datastore import store_manager

    db_mod.set_run_db(None)
    db_mod._run_db = None
    store_manager._db = None
    yield
    db_mod._run_db = None
    store_manager._db = None


@pytest.fixture()
def rundb_mock():
    """In-memory RunDB mock capturing calls (reference RunDBMock analog)."""
    from tests.mocks import RunDBMock

    import mlrun_tpu.db as db_mod

    mock = RunDBMock()
    db_mod.set_run_db(mock)
    yield mock
    db_mod._run_db = None


@pytest.fixture(scope="session")
def cpu_mesh8():
    from mlrun_tpu.parallel.mesh import make_mesh

    return make_mesh({"data": 2, "fsdp": 2, "tensor": 2})


@pytest.fixture()
def service(tmp_path, monkeypatch):
    """Run the service in a thread; yield (base_url, state)."""
    import asyncio
    import socket
    import threading

    from aiohttp import web

    from mlrun_tpu.config import mlconf
    from mlrun_tpu.db.sqlitedb import SQLiteRunDB
    from mlrun_tpu.service.app import ServiceState, build_app

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]

    mlconf.httpdb.port = port  # advertise the ephemeral port to resources
    db = SQLiteRunDB(str(tmp_path / "svc.sqlite"),
                     logs_dir=str(tmp_path / "logs"))
    state = ServiceState(db=db)
    loop = asyncio.new_event_loop()
    started = threading.Event()

    runner_box = {}

    async def serve2():
        runner = web.AppRunner(build_app(state))
        await runner.setup()
        runner_box["runner"] = runner
        site = web.TCPSite(runner, "127.0.0.1", port)
        await site.start()
        started.set()
        while not runner_box.get("stop"):
            await asyncio.sleep(0.05)
        await runner.cleanup()

    thread = threading.Thread(
        target=lambda: (asyncio.set_event_loop(loop),
                        loop.run_until_complete(serve2())),
        daemon=True)
    thread.start()
    assert started.wait(10)
    yield f"http://127.0.0.1:{port}", state
    runner_box["stop"] = True
    thread.join(timeout=5)
    loop.call_soon_threadsafe(loop.stop)


@pytest.fixture()
def http_db(service):
    from mlrun_tpu.db.httpdb import HTTPRunDB

    url, _ = service
    return HTTPRunDB(url).connect()
