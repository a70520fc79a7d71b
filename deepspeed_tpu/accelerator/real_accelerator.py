"""Accelerator singleton dispatch (reference ``accelerator/real_accelerator.py:37``)."""

import os

ds_accelerator = None


def _detect():
    name = os.environ.get("DS_ACCELERATOR")
    if name:
        return name
    # a backend that fails to come up is an error, never "cpu"
    import jax
    return "tpu" if jax.default_backend() == "tpu" else "cpu"


def get_accelerator():
    global ds_accelerator
    if ds_accelerator is not None:
        return ds_accelerator
    name = _detect()
    if name == "tpu":
        from .tpu_accelerator import TPU_Accelerator
        ds_accelerator = TPU_Accelerator()
    else:
        from .cpu_accelerator import CPU_Accelerator
        ds_accelerator = CPU_Accelerator()
    return ds_accelerator


def set_accelerator(accel):
    global ds_accelerator
    ds_accelerator = accel


def is_current_accelerator_supported():
    return True
