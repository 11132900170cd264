"""Test session config.

Device tests run on the CPU backend with 8 virtual devices so multi-chip
sharding logic (`shard_map`/`psum` over a Mesh) is exercised without a TPU
pod — the rebuild's analog of the reference testing multi-node behavior
against single-node containers (SURVEY.md §4). Must run before any jax
import anywhere in the test process.

NOTE: whatever the shell exported, the tests run on the CPU: a
``setdefault`` is not enough, so hard-override both the env var and the
jax config here and assert the result at session start (a run that lands
on a real chip breaks the 8-way meshes, and the explicit
``JAX_PLATFORMS=cpu`` is also what lets ``parallel/mesh.py`` build a mesh
without a TPU).
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")


def pytest_configure(config):
    # tier-1 runs `-m "not slow"` (ROADMAP.md); the chaos/soak tier is
    # opt-in. Registered here because the repo has no pytest.ini.
    config.addinivalue_line(
        "markers",
        "slow: long randomized chaos/soak tests, excluded from tier-1",
    )


def pytest_sessionstart(session):
    assert jax.default_backend() == "cpu", jax.default_backend()
    assert len(jax.devices()) == 8, jax.devices()
