"""Concrete TPU accelerator (the CUDA_Accelerator analogue,
reference ``accelerator/cuda_accelerator.py``)."""

import jax
import jax.numpy as jnp
import numpy as np

from .abstract_accelerator import DeepSpeedAccelerator


# Published per-chip peaks, keyed by ``jax.Device.device_kind``. A kind that
# is not here is an error: a utilization figured against another chip's peak
# is wrong by a factor nobody sees. (v5e's oft-quoted 394 is the int8 rate.)
CHIP_PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
}


class TPU_Accelerator(DeepSpeedAccelerator):

    def __init__(self):
        super().__init__()
        self._name = "tpu"
        self._communication_backend_name = "xla"
        self._seed = 0

    def _devices(self):
        return jax.local_devices()

    def device_name(self, device_index=None):
        if device_index is None:
            return "tpu"
        return f"tpu:{device_index}"

    def device(self, device_index=None):
        devs = self._devices()
        return devs[device_index or 0]

    def device_count(self):
        return jax.device_count()

    def local_device_count(self):
        return jax.local_device_count()

    def synchronize(self, device_index=None):
        jax.block_until_ready(jax.device_put(np.zeros(()), self.device(device_index)))

    def manual_seed(self, seed):
        self._seed = seed

    def rng_key(self):
        return jax.random.key(self._seed)

    def memory_stats(self, device_index=None):
        return self.device(device_index).memory_stats() or {}

    def is_bf16_supported(self):
        return True

    def is_fp16_supported(self):
        return True  # emulated via f32 accumulate; bf16 is the native type

    def supported_dtypes(self):
        return [jnp.float32, jnp.bfloat16, jnp.float16, jnp.int8, jnp.float8_e4m3fn, jnp.float8_e5m2]

    def communication_backend_name(self):
        return self._communication_backend_name

    def on_accelerator(self, tensor):
        try:
            return any(d.platform != "cpu" for d in tensor.devices())
        except Exception:
            return False

    def range_push(self, msg):
        ann = jax.profiler.TraceAnnotation(msg)
        ann.__enter__()
        self._range_stack = getattr(self, "_range_stack", [])
        self._range_stack.append(ann)

    def range_pop(self):
        stack = getattr(self, "_range_stack", [])
        if stack:
            stack.pop().__exit__(None, None, None)

    def device_kind(self):
        devs = self._devices()
        return devs[0].device_kind if devs else "unknown"

    def _peaks(self):
        kind = self.device_kind()
        if kind not in CHIP_PEAKS:
            raise KeyError(
                f"no published peaks on record for device_kind {kind!r}; add it "
                f"to accelerator.tpu_accelerator.CHIP_PEAKS with its source "
                f"(known: {sorted(CHIP_PEAKS)})")
        return CHIP_PEAKS[kind]

    def peak_flops(self, dtype=jnp.bfloat16):
        """Published per-chip bf16 matmul FLOP/s, for MFU math."""
        return self._peaks()["bf16_flops"]

    def peak_hbm_bandwidth(self):
        """Published per-chip HBM bandwidth (bytes/s), for roofline math."""
        return self._peaks()["hbm_bytes_per_s"]
