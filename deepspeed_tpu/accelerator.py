"""Accelerator abstraction shim — ``get_accelerator()`` API parity.

Reference parity: ``accelerator/abstract_accelerator.py`` (DeepSpeedAccelerator
ABC) + ``real_accelerator.py get_accelerator()`` — the reference dispatches
every device operation (streams, memory stats, op builders, dtype support)
through this interface so CUDA/XPU/NPU/CPU backends are swappable.

On TPU there is exactly one backend and JAX already abstracts it, so this shim
is thin by design: it exists so reference-style code (`get_accelerator().
device_count()`, `.memory_stats()`, `.synchronize()`) ports without edits,
not to re-wrap JAX.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp


class TPUAccelerator:
    """reference abstract_accelerator.py surface, TPU semantics."""

    _name = "tpu"
    _communication_backend_name = "xla"

    # ---- identity ----
    def device_name(self, device_index: Optional[int] = None) -> str:
        devs = jax.devices()
        if device_index is None:
            return jax.default_backend()
        d = devs[device_index]
        return getattr(d, "device_kind", d.platform)

    def device_count(self) -> int:
        return len(jax.devices())

    def current_device(self) -> int:
        return 0          # SPMD: one process drives all local devices

    def communication_backend_name(self) -> str:
        return self._communication_backend_name

    def is_available(self) -> bool:
        try:
            return len(jax.devices()) > 0
        except Exception:
            return False

    # ---- synchronization (reference synchronize/stream APIs) ----
    def synchronize(self, device_index: Optional[int] = None) -> None:
        """There are no user-visible streams under XLA; blocking on a
        freshly enqueued value drains the device's queue."""
        (jnp.zeros(()) + 0).block_until_ready()

    # ---- memory (reference memory_stats/memory_allocated family) ----
    def memory_stats(self, device_index: int = 0) -> Dict[str, Any]:
        d = jax.local_devices()[device_index]
        stats = getattr(d, "memory_stats", lambda: None)()
        return dict(stats or {})

    def memory_allocated(self, device_index: int = 0) -> int:
        return int(self.memory_stats(device_index).get("bytes_in_use", 0))

    def max_memory_allocated(self, device_index: int = 0) -> int:
        return int(self.memory_stats(device_index).get(
            "peak_bytes_in_use", 0))

    def total_memory(self, device_index: int = 0) -> int:
        return int(self.memory_stats(device_index).get("bytes_limit", 0))

    # ---- dtype support (reference is_bf16_supported etc.) ----
    def is_bf16_supported(self) -> bool:
        return True

    def is_fp16_supported(self) -> bool:
        return True       # supported; bf16 is the native fast path

    def supported_dtypes(self) -> List[Any]:
        return [jnp.float32, jnp.bfloat16, jnp.float16,
                jnp.float8_e4m3fn, jnp.float8_e5m2, jnp.int8]

    # ---- op builder surface (reference create_op_builder / get_op_builder) ----
    def op_report(self) -> str:
        from deepspeed_tpu import ops
        return ops.op_report()


_ACCELERATOR: Optional[TPUAccelerator] = None


def get_accelerator() -> TPUAccelerator:
    """reference accelerator/real_accelerator.py:get_accelerator."""
    global _ACCELERATOR
    if _ACCELERATOR is None:
        _ACCELERATOR = TPUAccelerator()
    return _ACCELERATOR
