"""How the program starts on the chip: one process per chip, no silent
CPU, a compile cache that stays put (ISSUE 22). Nothing here needs a
chip; ``chip_smoke.py`` is the proof on one."""

import os
import subprocess
import sys

import jax
import pytest

from zipkin_tpu.parallel import mesh as mesh_mod

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _fresh_interpreter(code: str) -> subprocess.CompletedProcess:
    return subprocess.run(
        [sys.executable, "-c", code], cwd=REPO, capture_output=True,
        text=True, timeout=120,
    )


# A chip belongs to one process. The spawned parse workers and reader
# processes, and chip_smoke.py's own process, must never import JAX:
# the server (or nobody) holds the chip.
@pytest.mark.parametrize("module", [
    "zipkin_tpu.tpu.mp_ingest",
    "zipkin_tpu.tpu.ring",
    "zipkin_tpu.serving.reader",
    "zipkin_tpu.serving.__main__",
    "chip_smoke",
    # the smoke's host oracles and codecs
    "tests.fixtures",
    "zipkin_tpu.storage.memory",
    "zipkin_tpu.model.proto3",
])
def test_module_stays_off_jax(module):
    r = _fresh_interpreter(
        f"import sys, {module}; "
        "sys.exit(3 if 'jax' in sys.modules else 0)"
    )
    assert r.returncode == 0, (
        f"importing {module} pulled in jax" if r.returncode == 3
        else r.stderr
    )


class _FakeDevice:
    def __init__(self, platform):
        self.platform = platform


def test_make_mesh_refuses_a_cpu_nobody_asked_for(monkeypatch):
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    with pytest.raises(RuntimeError, match="needs a TPU.*'cpu'"):
        mesh_mod.make_mesh(1)


@pytest.mark.parametrize("value", ["", "tpu,cpu", "tpu"])
def test_only_an_explicit_cpu_is_an_excuse(monkeypatch, value):
    monkeypatch.setenv("JAX_PLATFORMS", value)
    with pytest.raises(RuntimeError, match="needs a TPU"):
        mesh_mod.require_tpu([_FakeDevice("cpu")])


def test_make_mesh_accepts_cpu_when_asked_and_tpu_always(monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert mesh_mod.make_mesh(2).devices.shape == (2,)
    monkeypatch.delenv("JAX_PLATFORMS")
    mesh_mod.require_tpu([_FakeDevice("tpu")])  # no raise


@pytest.fixture
def cache_config():
    """Restore whatever the helper sets on jax.config."""
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_honours_the_environment(monkeypatch, tmp_path,
                                               cache_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    before = jax.config.jax_compilation_cache_dir
    assert mesh_mod.compile_cache_dir() == str(tmp_path)
    assert mesh_mod.enable_compile_cache() == str(tmp_path)
    # JAX reads the variable itself; code sets no other directory
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_the_checkout(monkeypatch, cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    fixed = os.path.join(REPO, ".jax_cache")
    assert mesh_mod.compile_cache_dir() == fixed
    # the tests' own JAX_PLATFORMS=cpu keeps it off
    assert mesh_mod.enable_compile_cache() is None
    monkeypatch.delenv("JAX_PLATFORMS")
    assert mesh_mod.enable_compile_cache() == fixed
    assert jax.config.jax_compilation_cache_dir == fixed
    # ... and git never sees it
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_native_parser_is_required_on_the_fast_path(monkeypatch):
    from zipkin_tpu import native
    from zipkin_tpu.server.app import ZipkinServer
    from zipkin_tpu.server.config import ServerConfig

    class _FastStore:
        def ingest_json_fast(self, data):  # what marks a fast-path store
            raise AssertionError("not reached")

    monkeypatch.setattr(native, "available", lambda: False)
    with pytest.raises(RuntimeError, match="native span parser"):
        ZipkinServer(ServerConfig(tpu_fast_ingest=True), storage=_FastStore())


def test_statusz_device_block_names_the_mesh_devices():
    from zipkin_tpu.obs.device import OBSERVATORY

    devices = jax.devices()[:4]
    body = OBSERVATORY.status(devices)
    assert body["platform"] == "cpu"
    assert body["deviceKind"] == devices[0].device_kind
    assert body["count"] == 4
    assert "analysisFailures" in body["totals"]
    assert "compileCacheDir" in body
    assert "platform" not in OBSERVATORY.status()


def test_analysis_failure_is_counted_not_hidden():
    from zipkin_tpu.obs.device import DeviceObservatory

    obs = DeviceObservatory(enabled=True, analysis=True)
    jitted = jax.jit(lambda x: x + 1)

    class _NoLower:
        """A jitted program whose AOT re-lowering fails."""

        _cache_size = staticmethod(jitted._cache_size)

        def __call__(self, x):
            return jitted(x)

        def lower(self, *a, **kw):
            raise ValueError("boom")

    wrapped = obs.wrap("prog", _NoLower())
    assert int(wrapped(1)) == 2
    assert obs.totals()["analysisFailures"] == 1
    assert "boom" in obs.programs()["prog"]["analysisError"]


def test_hbm_stats_keeps_per_device_figures():
    from zipkin_tpu.obs.device import hbm_stats

    class _Dev:
        def __init__(self, i, used):
            self.id, self._used = i, used

        def memory_stats(self):
            return {"bytes_in_use": self._used, "bytes_limit": 100,
                    "peak_bytes_in_use": self._used + 1}

    got = hbm_stats([_Dev(0, 10), _Dev(1, 20)])
    assert got["bytesInUse"] == 30 and got["devices"] == 2
    assert [d["bytesInUse"] for d in got["perDevice"]] == [10, 20]
    assert [d["id"] for d in got["perDevice"]] == [0, 1]
