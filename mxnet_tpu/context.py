"""Device contexts (reference: python/mxnet/context.py, include/mxnet/base.h).

TPU-native redesign: a ``Context`` names a JAX device.  The reference's
Context{cpu, gpu(i), cpu_pinned} maps onto JAX's platform/device-index model:

- ``mx.cpu(i)``      → jax CPU device i (host; with XLA_FLAGS
                        --xla_force_host_platform_device_count=N there are N,
                        which is how multi-device semantics are tested without
                        accelerators — same trick as the reference's
                        tests/python/unittest/test_multi_device_exec.py on
                        mx.cpu(0)/mx.cpu(1)).
- ``mx.tpu(i)``      → jax TPU chip i — the first-class accelerator here.
- ``mx.gpu(i)``      → alias for the i-th available accelerator so that
                        reference scripts written against mx.gpu() run
                        unchanged on TPU.

There is no storage manager / pinned-memory tier to manage (reference
src/storage/): XLA owns HBM, and host↔device transfer staging is handled by
jax.device_put; this is the engine/storage collapse documented in SURVEY §7.
"""
from __future__ import annotations

import threading

__all__ = ["Context", "cpu", "gpu", "tpu", "current_context", "num_gpus",
           "num_tpus", "cpu_pinned"]

_devtype2str = {1: "cpu", 2: "gpu", 3: "cpu_pinned", 4: "cpu_shared", 5: "tpu"}
_devstr2type = {v: k for k, v in _devtype2str.items()}


def _jax():
    import jax
    return jax


class Context:
    """A device context.  Hashable, comparable, usable with ``with`` to set
    the default context (reference python/mxnet/context.py:22-121)."""

    _default_ctx = threading.local()

    def __init__(self, device_type, device_id=0):
        if isinstance(device_type, Context):
            self.device_typeid = device_type.device_typeid
            self.device_id = device_type.device_id
        else:
            self.device_typeid = _devstr2type[device_type]
            self.device_id = device_id
        self._old_ctx = None

    @property
    def device_type(self):
        return _devtype2str[self.device_typeid]

    def __hash__(self):
        return hash((self.device_typeid, self.device_id))

    def __eq__(self, other):
        return (isinstance(other, Context)
                and self.device_typeid == other.device_typeid
                and self.device_id == other.device_id)

    def __str__(self):
        return "%s(%d)" % (self.device_type, self.device_id)

    __repr__ = __str__

    def __enter__(self):
        self._old_ctx = getattr(Context._default_ctx, "value", None)
        Context._default_ctx.value = self
        return self

    def __exit__(self, *args):
        Context._default_ctx.value = self._old_ctx

    # -- jax mapping -------------------------------------------------------
    def jax_device(self):
        """Resolve to the concrete jax.Device this context names.

        Uses process-LOCAL devices: under jax.distributed each process only
        addresses its own chips (global devices exist but are not
        addressable), matching the reference's per-worker device numbering.
        """
        jax = _jax()
        dt = self.device_type
        if dt in ("cpu", "cpu_pinned", "cpu_shared"):
            devs = (jax.local_devices(backend="cpu") if _has_platform("cpu")
                    else jax.local_devices())
        elif dt == "tpu":
            devs = jax.local_devices(backend="tpu")
        else:  # 'gpu' → any accelerator (tpu preferred)
            devs = _accelerators()
            if not devs:
                raise RuntimeError(
                    "%s: this process has no accelerator backend (jax "
                    "sees only %s); use mx.cpu() to run on the host"
                    % (self, jax.default_backend()))
        if self.device_id >= len(devs):
            raise ValueError("%s: device_id out of range (%d available)"
                             % (self, len(devs)))
        return devs[self.device_id]

    @property
    def real_device_type(self):
        """Resolved jax platform ('cpu'/'tpu'/...)."""
        return self.jax_device().platform

    def empty_cache(self):
        """Reference releases pooled GPU memory; XLA owns its own allocator,
        so this is a no-op kept for API parity."""


def _has_platform(name):
    jax = _jax()
    try:
        return bool(jax.devices(name))
    except RuntimeError:
        return False


def _accelerators():
    jax = _jax()
    for plat in ("tpu", "gpu", "cuda", "rocm"):
        try:
            devs = jax.local_devices(backend=plat)
            if devs:
                return devs
        except RuntimeError:
            continue
    return []


def cpu(device_id=0):
    return Context("cpu", device_id)


def cpu_pinned(device_id=0):
    return Context("cpu_pinned", device_id)


def gpu(device_id=0):
    """Alias context for the i-th accelerator (TPU here). Keeps reference
    scripts (`mx.gpu(0)`) runnable unchanged."""
    return Context("gpu", device_id)


def tpu(device_id=0):
    return Context("tpu", device_id)


def num_gpus():
    return len(_accelerators())


def num_tpus():
    jax = _jax()
    try:
        return len(jax.devices("tpu"))
    except RuntimeError:
        return 0


def current_context():
    v = getattr(Context._default_ctx, "value", None)
    return v if v is not None else Context("cpu", 0)
