"""Test session config.

Sets up a virtual 8-device CPU platform BEFORE jax is imported anywhere, so
multi-chip sharding tests (mesh/pjit/shard_map) run without TPU hardware.
Also wires the reference-style CLI flags (--preset/--fork/--disable-bls)
(reference: tests/core/pyspec/eth2spec/test/conftest.py:30-93).
"""

# Tests run on the CPU with 8 virtual devices, whatever the environment
# says: the chip is reached only through chip_smoke.py.
from consensus_specs_tpu.utils.jax_env import force_cpu  # noqa: E402

force_cpu(n_devices=8)

import pytest  # noqa: E402


def pytest_addoption(parser):
    parser.addoption(
        "--preset", action="store", type=str, default="minimal",
        help="preset to run tests against: minimal or mainnet",
    )
    parser.addoption(
        "--fork", action="append", type=str, default=None,
        help="fork(s) to run tests against (repeatable)",
    )
    parser.addoption(
        "--disable-bls", action="store_true", default=True,
        help="disable BLS for tests that do not require it (the default, "
        "mirroring the reference's `make test`, reference Makefile:100; "
        "@always_bls tests still run real BLS)",
    )
    parser.addoption(
        "--enable-bls", action="store_true", default=False,
        help="run every test with real BLS (reference `make citest` mode)",
    )
    parser.addoption(
        "--run-slow", action="store_true", default=False,
        help="run tests marked slow (long XLA compiles / big batches)",
    )
    parser.addoption(
        "--bls-type", action="store", type=str, default="py_ecc",
        help="BLS backend: py_ecc (pure-python oracle) or tpu (JAX backend)",
    )


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long XLA compiles / large batches; needs --run-slow"
    )


def pytest_collection_modifyitems(config, items):
    if config.getoption("--run-slow"):
        return
    skip_slow = pytest.mark.skip(reason="slow test: pass --run-slow to run")
    for item in items:
        if "slow" in item.keywords:
            item.add_marker(skip_slow)


@pytest.fixture(autouse=True)
def _configure_harness(request):
    from consensus_specs_tpu.test import context
    from consensus_specs_tpu.utils import bls

    context.DEFAULT_TEST_PRESET = request.config.getoption("--preset")
    forks = request.config.getoption("--fork")
    context.DEFAULT_PYTEST_FORKS = set(forks) if forks else None
    # default: BLS off except @always_bls (reference `make test`,
    # Makefile:100); --enable-bls mirrors `make citest` (Makefile:111)
    context.DEFAULT_BLS_ACTIVE = bool(request.config.getoption("--enable-bls"))
    bls_type = request.config.getoption("--bls-type")
    if bls_type == "tpu":
        bls.use_tpu()
    else:
        bls.use_py_ecc()
    yield
