"""Device places.

Analog of the reference's Place hierarchy
(/root/reference/paddle/phi/common/place.h). On TPU the set collapses to
{TPUPlace, CPUPlace}; a place resolves to a concrete jax.Device. Device
discovery goes through PJRT (jax.devices) rather than a dynloaded driver.
"""
from __future__ import annotations

import functools

import jax


class Place:
    device_type = "unknown"

    def __init__(self, device_id: int = 0):
        self.device_id = int(device_id)

    def __repr__(self):
        return "Place(%s:%d)" % (self.device_type, self.device_id)

    def __eq__(self, other):
        return (
            isinstance(other, Place)
            and self.device_type == other.device_type
            and self.device_id == other.device_id
        )

    def __hash__(self):
        return hash((self.device_type, self.device_id))

    def jax_device(self) -> jax.Device:
        devs = _devices_by_type(self.device_type)
        if not devs:
            raise RuntimeError(
                "No %s devices visible to PJRT" % self.device_type
            )
        # no wrap-around: TPUPlace(3) on a one-chip host is an error,
        # not chip 0 under another name
        if not 0 <= self.device_id < len(devs):
            raise RuntimeError(
                "%r: device id out of range, PJRT sees %d %s device(s)"
                % (self, len(devs), self.device_type))
        return devs[self.device_id]


class TPUPlace(Place):
    device_type = "tpu"


class CPUPlace(Place):
    device_type = "cpu"

    def __init__(self):
        super().__init__(0)


class CUDAPlace(Place):
    """Accepted for API compatibility; resolves to the accelerator backend."""

    device_type = "tpu"


@functools.lru_cache(maxsize=None)
def _devices_by_type(device_type: str):
    if device_type == "cpu":
        try:
            return tuple(jax.devices("cpu"))
        except RuntimeError:
            return tuple(jax.devices())
    if device_type.startswith("custom:"):
        # a registered PJRT plugin's OWN devices — never another backend
        return tuple(jax.devices(device_type.split(":", 1)[1]))
    # "tpu" means "the accelerator backend" — whatever PJRT says is
    # default. A host with no accelerator has no such devices: resolving
    # an accelerator place to a CPU device would hide that.
    return tuple(d for d in jax.devices() if d.platform != "cpu")


def is_compiled_with_cuda():  # API-compat shim: this framework targets TPU
    return False


def is_compiled_with_tpu():
    return True


def device_count() -> int:
    return len(jax.devices())


_current_place = None


def place_for(device, default_idx=0):
    """Parse a device string into a Place: 'cpu', 'tpu:1', a registered
    custom device type ('fake_cpu:0'), or 'custom:<type>:<id>'. Vendor
    aliases map to the accelerator backend."""
    if isinstance(device, Place):
        return device
    name = str(device)
    explicit_custom = name.startswith("custom:")
    if explicit_custom:
        name = name[len("custom:"):]
    kind, _, idx = name.partition(":")
    idx = int(idx) if idx else default_idx
    if explicit_custom and kind not in _custom_devices:
        raise ValueError(
            "place_for: custom device type %r is not registered "
            "(registered: %s)" % (kind, sorted(_custom_devices) or "none"))
    kind = {"gpu": "tpu", "cuda": "tpu", "xpu": "tpu",
            "npu": "tpu"}.get(kind, kind)
    if kind == "cpu":
        return CPUPlace()
    if kind in _custom_devices:
        return CustomPlace(kind, idx)
    return TPUPlace(idx)


def set_device(device):
    """paddle.set_device analog (reference python/paddle/device/__init__.py).
    Accepts 'cpu' / 'tpu[:i]' / vendor aliases / a registered custom
    device type name (reference paddle.set_device('custom_cpu:0'))."""
    global _current_place
    _current_place = place_for(device)
    return _current_place


def get_device():
    p = _get_current_place()
    return "%s:%d" % (p.device_type, p.device_id)


def _get_current_place() -> Place:
    global _current_place
    if _current_place is None:
        devs = jax.devices()
        _current_place = (
            CPUPlace() if devs[0].platform == "cpu" else TPUPlace(0)
        )
    return _current_place


# -- custom-device plugin ABI ------------------------------------------------
#
# Parity: reference DeviceInterface plugin runtime
# (phi/backends/custom/custom_device.cc, device_base.h:31 — ~50 virtuals
# for memory/stream/event/CCL, registered from a dlopen'd vendor .so).
# TPU-native: PJRT *is* the device plugin ABI — a vendor ships a PJRT
# plugin .so and jax loads it; memory/streams/events/collectives all come
# through the PJRT C API, so the reference's hand-rolled virtual table is
# the part XLA already standardized.

_custom_devices = {}


class CUDAPinnedPlace(Place):
    """API-compat shim: pinned host memory is a CUDA transfer concept;
    PJRT host buffers play that role here."""

    device_type = "cpu"

    def __init__(self):
        super().__init__(0)


class NPUPlace(Place):
    """API-compat shim (reference NPU vendor place; no such backend)."""

    device_type = "npu"

    def __init__(self, device_id=0):
        super().__init__(device_id)


class CustomPlace(Place):
    """reference phi::CustomPlace (plugin device placement)."""

    def __init__(self, device_type, device_id=0):
        super().__init__(device_id)
        self.device_type = "custom:%s" % device_type
        self.custom_type = device_type


def register_custom_device(device_type, pjrt_plugin_path, options=None):
    """Register a PJRT plugin .so as a custom device backend (reference
    DeviceManager::Register + LoadCustomRuntimeLib,
    phi/backends/custom/custom_device.cc:1040).

    Must run BEFORE any jax backend initialization — PJRT plugin
    discovery is frozen at first use (the reference dlopens vendor libs
    at framework init for the same reason)."""
    from jax._src import xla_bridge

    if xla_bridge.backends_are_initialized():
        raise RuntimeError(
            "register_custom_device(%r) called after the JAX runtime "
            "initialized; plugin discovery is frozen at first backend "
            "use. Register custom devices before any op/mesh/device "
            "call (e.g. right after import)." % device_type)
    xla_bridge.register_plugin(device_type,
                               library_path=pjrt_plugin_path,
                               options=options or {})
    _custom_devices[device_type] = pjrt_plugin_path
    _devices_by_type.cache_clear()
    return CustomPlace(device_type, 0)


def register_custom_device_factory(device_type, factory, priority=-100):
    """Register a custom backend from an in-process PJRT client factory.

    This is the TESTING/prototyping path — the analog of the reference's
    fake plugin device (phi/backends/custom/fake_cpu_device.h:1, used by
    custom_device_test.cc to prove the plugin runtime without hardware).
    Real hardware ships a PJRT C-API .so through register_custom_device.
    Negative priority keeps the plugged backend from stealing the
    default-platform slot."""
    from jax._src import xla_bridge

    if xla_bridge.backends_are_initialized():
        raise RuntimeError(
            "register_custom_device_factory(%r) called after the JAX "
            "runtime initialized; register before any op/mesh/device "
            "call." % device_type)
    xla_bridge.register_backend_factory(device_type, factory,
                                        priority=priority)
    _custom_devices[device_type] = "<factory>"
    _devices_by_type.cache_clear()
    return CustomPlace(device_type, 0)


def register_fake_cpu_device(device_type="fake_cpu"):
    """The reference fake_cpu_device analog: registers a host-memory PJRT
    client under its own platform name so the whole custom-device path
    (registration -> discovery -> placement -> compiled execution) is
    testable on any machine."""

    def factory():
        from jax._src.lib import xla_client

        return xla_client.make_cpu_client()

    return register_custom_device_factory(device_type, factory)


def get_all_custom_device_type():
    """reference paddle.device.get_all_custom_device_type."""
    return sorted(_custom_devices)


def is_compiled_with_custom_device(device_type):
    return device_type in _custom_devices
