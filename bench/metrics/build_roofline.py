"""Roofline share of the build kernels (``gram_chol_kernel`` and
``cross_solve_kernel``) over the fits of the window."""
import cost
import layers

KERNELS = ("gram_chol_kernel", "cross_solve_kernel")


def read(reading):
    fits = len(layers.spans(reading, "fit"))
    per_fit = cost.fit_launches(reading.cfg)
    launches = [l for k in KERNELS for l in per_fit[k]] * fits
    return layers.roofline_pct(reading, launches, KERNELS)
