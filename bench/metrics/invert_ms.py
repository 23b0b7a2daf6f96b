"""Device time of Algorithm 2 (``invert_with_leaf``: ``leaf_factor`` and
the middle-factor tail) per fit."""
import layers


def read(reading):
    return layers.module_ms(reading, "jit_invert_with_leaf", "fit")
