"""95th percentile of PUT latency over every PUT of the window: first byte
sent to the 200 that acknowledges it at write quorum (client side)."""
import window


def read(run):
    return window.latency_ms(run, "PUT", 0.95)
