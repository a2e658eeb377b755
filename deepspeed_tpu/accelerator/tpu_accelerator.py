"""TPU accelerator implementation.

The TPU analog of the reference's ``accelerator/hpu_accelerator.py`` (285 LoC,
which maps the DeepSpeedAccelerator surface onto ``habana_frameworks.torch.hpu``
and declares ``_communication_backend_name='hccl'`` at line 19). Here the
surface maps onto JAX platform/device APIs and the communication backend is
'xla' — collectives compile into the program and ride ICI/DCN.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np

from .abstract_accelerator import DeepSpeedAccelerator


class TPU_Accelerator(DeepSpeedAccelerator):

    def __init__(self, platform="tpu"):
        super().__init__()
        self._name = platform
        self._platform = platform
        self._communication_backend_name = "xla"
        self._current_device_index = 0
        self._seed = 0
        self._rng_key = jax.random.PRNGKey(0)
        self._fence = None  # (jitted trivial computation, one operand per local device)

    # ---- Device APIs ----
    def is_synchronized_device(self):
        return False

    def _devices(self):
        # raises when the platform asked for (or detected) has no devices:
        # counting another platform's devices would hide the missing one
        return jax.devices(self._platform)

    def _local_devices(self):
        return [d for d in self._devices() if d.process_index == jax.process_index()]

    def device_name(self, device_index=None):
        if device_index is None:
            return self._name
        return f"{self._name}:{device_index}"

    def device(self, device_index=None):
        devs = self._local_devices()
        return devs[device_index if device_index is not None else self._current_device_index]

    def set_device(self, device_index):
        self._current_device_index = device_index

    def current_device(self):
        return self._current_device_index

    def current_device_name(self):
        return f"{self._name}:{self._current_device_index}"

    def device_count(self):
        return len(self._local_devices())

    def global_device_count(self):
        return len(self._devices())

    def synchronize(self, device_index=None):
        """Wait until everything already enqueued on the local devices has
        run. A device runs its programs in the order they were enqueued, so
        the wait is for one trivial computation enqueued now on each of them
        (``jax.effects_barrier()`` alone waits only for computations with
        side effects, and returns while ordinary ones are still queued)."""
        if self._fence is None:
            self._fence = (jax.jit(lambda x: x + 1),
                           [jax.device_put(np.int32(0), d) for d in self._local_devices()])
        fn, operands = self._fence
        jax.block_until_ready([fn(x) for x in operands])
        jax.effects_barrier()

    # ---- RNG APIs ----
    def manual_seed(self, seed):
        self._seed = int(seed)
        self._rng_key = jax.random.PRNGKey(self._seed)

    def initial_seed(self):
        return self._seed

    def rng_key(self):
        return self._rng_key

    def split_rng_key(self, num=2):
        keys = jax.random.split(self._rng_key, num + 1)
        self._rng_key = keys[0]
        return keys[1:]

    # ---- Memory management ----
    def empty_cache(self):
        # XLA manages HBM via BFC allocator; explicit GC of donated buffers:
        try:
            jax.clear_caches()
        except Exception:
            pass

    def _stats(self, device_index=None):
        try:
            return self.device(device_index).memory_stats() or {}
        except Exception:
            return {}

    def memory_allocated(self, device_index=None):
        return self._stats(device_index).get("bytes_in_use", 0)

    def max_memory_allocated(self, device_index=None):
        return self._stats(device_index).get("peak_bytes_in_use", 0)

    def reset_peak_memory_stats(self, device_index=None):
        # jax exposes no reset; record a watermark instead.
        self._peak_watermark = self.memory_allocated(device_index)

    def memory_stats(self, device_index=None):
        return self._stats(device_index)

    def total_memory(self, device_index=None):
        s = self._stats(device_index)
        return s.get("bytes_limit", s.get("bytes_reservable_limit", 0))

    def available_memory(self, device_index=None):
        return self.total_memory(device_index) - self.memory_allocated(device_index)

    # ---- Data types ----
    def is_bf16_supported(self):
        return True

    def is_fp16_supported(self):
        # fp16 matmuls are emulated on TPU; supported but bf16 is preferred.
        return True

    def supported_dtypes(self):
        return [jnp.float32, jnp.bfloat16, jnp.float16, jnp.int8, jnp.int32]

    def preferred_dtype(self):
        return jnp.bfloat16

    # ---- Communication backend ----
    def communication_backend_name(self):
        return self._communication_backend_name

    # ---- Tracing ----
    def range_push(self, msg):
        try:
            self._trace_ctx = jax.profiler.TraceAnnotation(msg)
            self._trace_ctx.__enter__()
        except Exception:
            self._trace_ctx = None

    def range_pop(self):
        ctx = getattr(self, "_trace_ctx", None)
        if ctx is not None:
            ctx.__exit__(None, None, None)
            self._trace_ctx = None

    # ---- Op builder ----
    def op_builder_dir(self):
        return "deepspeed_tpu.ops"

    def create_op_builder(self, class_name):
        # the registry holds ready singleton builders (there is nothing to
        # JIT-compile per instance on TPU), so "create" returns the handle;
        # a class (e.g. a user-registered builder type) is instantiated
        builder = self.get_op_builder(class_name)
        return builder() if isinstance(builder, type) else builder

    def get_op_builder(self, class_name):
        from deepspeed_tpu.ops import op_registry

        return op_registry.get(class_name)

    # ---- Capabilities ----
    def is_available(self):
        try:
            return len(jax.devices(self._platform)) > 0
        except RuntimeError:
            return False

    def supports_pallas(self):
        return self._platform == "tpu"

    # ---- Convenience ----
    def platform(self):
        return self._platform

    def pin_memory(self, array):
        """Host arrays in JAX are staged through pinned buffers by the runtime;
        this mirrors the reference API (``abstract_accelerator.py:233``) as a
        pass-through that ensures a contiguous ndarray."""
        return np.ascontiguousarray(array)
