"""Device time of the partition program (``build_partition``) per fit."""
import layers


def read(reading):
    return layers.module_ms(reading, "jit_build_partition", "fit")
