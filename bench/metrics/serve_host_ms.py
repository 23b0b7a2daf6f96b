"""Time per served batch in which no device op ran: validation, padding,
transfer, the finiteness probe and the loop."""
import layers


def read(reading):
    return layers.host_ms(reading, "serve")
