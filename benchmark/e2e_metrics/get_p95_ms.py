"""95th percentile of GET latency over every GET of the window: request
sent to last body byte hashed (client side)."""
import window


def read(run):
    return window.latency_ms(run, "GET", 0.95)
