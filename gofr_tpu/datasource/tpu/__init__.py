"""The ``tpu`` datasource — the native core of this build.

BASELINE.json north star: ``ctx.tpu.execute(...)`` inside ordinary handlers.
The reference has no accelerator; SURVEY §2.9 maps the requirement: device/
topology discovery, executable compile-or-load cache, execution with device
buffers, HBM stats into health/metrics, all behind the provider pattern so
the Container wires it like any datasource.

Backend: JAX's PJRT runtime (libtpu on TPU, the CPU client for dev/CI —
``JAX_PLATFORMS`` selects, SURVEY §7 phase 3). ``TPU_PJRT_PLUGIN`` is a
PATH to a plugin ``.so`` for the native binding (native/pjrt.py), never a
platform name.
"""

from gofr_tpu.datasource.tpu.client import TPUClient, new_tpu

__all__ = ["TPUClient", "new_tpu"]
