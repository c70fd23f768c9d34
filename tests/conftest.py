"""Shared fixtures. NOTE: no XLA_FLAGS here — tests run on the single real
CPU device; multi-device sharding checks run in a subprocess (see
test_sharding.py) so the main process never locks a 512-device backend."""
import jax
import pytest


#: skip-on-CPU marker for tests that need a TPU backend (HLO cost models,
#: multi-device topologies), per docs/LIMITATIONS.md
needs_accelerator = pytest.mark.skipif(
    jax.devices()[0].platform == "cpu",
    reason="needs the TPU backend: HLO cost models and multi-device "
           "topologies differ on the CPU backend (docs/LIMITATIONS.md)")


@pytest.fixture(scope="session")
def key():
    return jax.random.PRNGKey(0)


@pytest.fixture(scope="session", autouse=True)
def _f64_off():
    jax.config.update("jax_enable_x64", False)
