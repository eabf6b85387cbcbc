"""Test harness: an 8-device virtual CPU mesh — the "fake cluster".

SURVEY.md §7 test strategy: distributed behavior is tested with forced host
devices so no TPU is needed in CI.  jax may already be imported when pytest
starts, so besides the env vars we flip ``jax.config`` here, which is
honored because no backend has been initialized yet at collection time.
"""

import os

# For the compile-only tests (a described TPU, test_chip_compile.py and
# test_aot_tpu_compile.py): keep libtpu's logs out of /tmp and, off GCP,
# its metadata polls off the network.
os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("TPU_SKIP_MDS_QUERY", "1")

# TPUFRAME_TPU_TESTS=1 keeps the real backend so the TPU-gated tests
# (tests/test_flash_attention_tpu.py) can run on a chip through the chip
# tool:
#   chiprun -- env TPUFRAME_TPU_TESTS=1 python -m pytest \
#       tests/test_flash_attention_tpu.py -o addopts=
_USE_TPU = os.environ.get("TPUFRAME_TPU_TESTS") == "1"

if not _USE_TPU:
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8"
    )
    os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402

if not _USE_TPU:
    jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: multi-process / long-running tests")


def pytest_collection_modifyitems(config, items):
    if not _USE_TPU:
        return
    # TPU mode: run ONLY the TPU-gated tests and skip everything that
    # expects the 8-device virtual CPU cluster.
    skip = pytest.mark.skip(
        reason="TPUFRAME_TPU_TESTS=1 runs only the *_tpu test modules")
    for item in items:
        if not item.fspath.basename.endswith("_tpu.py"):
            item.add_marker(skip)


@pytest.fixture
def no_persistent_compile_cache():
    """An executable compiled for a described (unattached) TPU is written
    to the persistent cache but cannot be read back without a chip — every
    later run would warn and recompile — so compile-only tests keep the
    cache off around themselves."""
    from tpuframe.utils import compile_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compile_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compile_cache.reset_cache()


@pytest.fixture(scope="module")
def v5e_topology():
    """A described v5e:2x2 (four compile-only devices), or skip."""
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no libtpu / no such topology
        pytest.skip(f"cannot describe a v5e topology here: {e}")


@pytest.fixture(scope="session")
def mesh8():
    from tpuframe.parallel import mesh as mesh_lib

    assert len(jax.devices()) == 8, "expected 8 virtual CPU devices"
    return mesh_lib.make_mesh(mesh_lib.MeshSpec(data=8))


@pytest.fixture(scope="session")
def mesh42():
    """2-D mesh: 4-way data x 2-way model — exercises non-trivial axes."""
    from tpuframe.parallel import mesh as mesh_lib

    return mesh_lib.make_mesh(mesh_lib.MeshSpec(data=4, model=2))
