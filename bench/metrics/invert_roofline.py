"""Roofline share of the leaf factorization kernel (``hck_leaf_factor``)
over the fits of the window."""
import cost
import layers

KERNELS = ("hck_leaf_factor",)


def read(reading):
    fits = len(layers.spans(reading, "fit"))
    launches = cost.fit_launches(reading.cfg)["hck_leaf_factor"] * fits
    return layers.roofline_pct(reading, launches, KERNELS)
