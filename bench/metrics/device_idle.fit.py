"""Percent of the traced fit window in which no device op ran."""
import layers


def read(reading):
    return layers.idle_pct(reading) if layers.spans(reading, "fit") else None
