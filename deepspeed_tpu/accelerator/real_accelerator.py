"""Accelerator selection.

Analog of the reference ``accelerator/real_accelerator.py:51-179``:
``get_accelerator()`` singleton with env override (``DS_ACCELERATOR``, same
variable name as the reference) followed by auto-detection, plus
``set_accelerator()`` for injection (reference :182). Supported names here:
``['tpu', 'cpu', 'gpu']`` (gpu = jax CUDA backend, for parity testing only).
"""

import os

SUPPORTED_ACCELERATOR_LIST = ["tpu", "cpu", "gpu"]

ds_accelerator = None


def _validate_accelerator(accel_obj):
    from .abstract_accelerator import DeepSpeedAccelerator

    if not isinstance(accel_obj, DeepSpeedAccelerator):
        raise AssertionError(f"{accel_obj.__class__.__name__} accelerator is not subclass of DeepSpeedAccelerator")


def is_current_accelerator_supported():
    return get_accelerator().device_name() in SUPPORTED_ACCELERATOR_LIST


def get_accelerator():
    global ds_accelerator
    if ds_accelerator is not None:
        return ds_accelerator

    accelerator_name = os.environ.get("DS_ACCELERATOR", None)
    if accelerator_name is not None:
        if accelerator_name not in SUPPORTED_ACCELERATOR_LIST:
            raise ValueError(f"accelerator_name {accelerator_name} value is not supported. "
                             f"Supported list: {SUPPORTED_ACCELERATOR_LIST}")
    else:
        # Auto-detect from the backend JAX initialised. No fallback: a backend
        # that fails to initialise raises here, and one this package does not
        # know is an error — never a silent "cpu" that hides the device.
        import jax

        accelerator_name = jax.default_backend()
        if accelerator_name not in SUPPORTED_ACCELERATOR_LIST:
            raise RuntimeError(f"JAX default backend {accelerator_name!r} is not a supported "
                               f"accelerator {SUPPORTED_ACCELERATOR_LIST}")

    if accelerator_name == "tpu":
        from .tpu_accelerator import TPU_Accelerator

        ds_accelerator = TPU_Accelerator()
    elif accelerator_name == "gpu":
        from .tpu_accelerator import TPU_Accelerator

        ds_accelerator = TPU_Accelerator(platform="gpu")
    else:
        from .cpu_accelerator import CPU_Accelerator

        ds_accelerator = CPU_Accelerator()
    _validate_accelerator(ds_accelerator)
    return ds_accelerator


def set_accelerator(accel_obj):
    global ds_accelerator
    _validate_accelerator(accel_obj)
    ds_accelerator = accel_obj
