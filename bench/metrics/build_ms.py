"""Device time of the factor build (``_build_on_tree``: landmarks,
``build_gram``, ``build_cross``) per fit."""
import layers


def read(reading):
    return layers.module_ms(reading, "jit__build_on_tree", "fit")
