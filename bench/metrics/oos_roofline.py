"""Roofline share of the OOS kernels over the batches of the window, at
the rows they compute (the padded bucket)."""
import cost
import layers


def read(reading):
    launches = [l for _, bucket, _ in layers.served_batches(reading)
                for l in cost.serve_launches(reading.cfg, bucket)]
    return layers.roofline_pct(reading, launches, ("oos_contract_kernel",))
