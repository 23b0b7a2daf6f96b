"""Device time of the OOS kernels (``oos_local`` + ``oos_walk``, one
``oos_contract_kernel`` each) per served batch."""
import layers


def read(reading):
    return layers.per_unit_ms(
        reading, layers.kernel_ns(reading, ("oos_contract_kernel",)), "serve")
