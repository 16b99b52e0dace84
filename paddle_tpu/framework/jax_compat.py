"""What this package takes from jax internals, kept in one module so that
a jax upgrade that moves one of them is a one-line fix."""
from jax._src import hardware_utils as _hardware_utils
from jax._src.core import trace_state_clean


def tracing() -> bool:
    """True when called under a jax trace (jit/vjp/shard_map)."""
    return not trace_state_clean()


def tpu_chips_on_host() -> int:
    """TPU chips on this host's PCI bus, counted WITHOUT initialising a
    backend — a parent process that must not take the chip (the
    launcher) can ask."""
    return _hardware_utils.num_available_tpu_chips_and_device_id()[0]
