"""Device time of the refined solve (``solve_with_inverse``) per fit."""
import layers


def read(reading):
    return layers.module_ms(reading, "jit_solve_with_inverse", "fit")
