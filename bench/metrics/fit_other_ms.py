"""Time per fit in which no device op ran: the fit's host path."""
import layers


def read(reading):
    return layers.host_ms(reading, "fit")
