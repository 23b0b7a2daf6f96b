"""Percent of the traced serving window in which no device op ran."""
import layers


def read(reading):
    return layers.idle_pct(reading) if layers.spans(reading, "serve") \
        else None
