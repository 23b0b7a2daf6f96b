"""Counted flops of the fits of the window over their time at the
chip's bf16 peak, in percent."""
import cost
import layers


def read(reading):
    fits = len(layers.spans(reading, "fit"))
    seconds = layers.unit_seconds(reading, "fit")
    if not fits or seconds <= 0:
        return None
    flops = cost.fit_flops(reading.cfg) * fits
    return 100.0 * flops / seconds / reading.peak["bf16_flops_per_s"]
