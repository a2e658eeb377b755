"""On-TPU kernel test suite: interpret-mode CI cannot catch Mosaic
miscompiles (e.g. the dynamic fori_loop trip-count NaNs found on-chip).
Unlike tests/conftest.py this does NOT force the CPU backend. Run it on
purpose, on a machine with a TPU, in a process of its own:

    python -m pytest tests_tpu -q

With no TPU the run is an ERROR (exit code 4), never a page of skips: a
suite that exists to prove the kernels on hardware must not report success
without hardware."""

import pytest


def pytest_configure(config):
    import jax

    from deepspeed_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise pytest.UsageError(
            f"tests_tpu needs a TPU: JAX found {len(devices)} x {devices[0].platform} "
            f"({devices[0].device_kind}). Run it on the chip, one process per chip.")
