"""Counted flops of the served batches (routing and both OOS launches at
the bucket's rows) over their service time at the chip's bf16 peak, in
percent."""
import cost
import layers


def read(reading):
    batches = layers.served_batches(reading)
    seconds = layers.unit_seconds(reading, "serve")
    if not batches or seconds <= 0:
        return None
    flops = sum(cost.serve_flops(reading.cfg, b) for _, b, _ in batches)
    return 100.0 * flops / seconds / reading.peak["bf16_flops_per_s"]
