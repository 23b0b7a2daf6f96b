"""Device time of the prediction plan (``oos.prepare``) per fit."""
import layers


def read(reading):
    return layers.module_ms(reading, "jit_prepare", "fit")
