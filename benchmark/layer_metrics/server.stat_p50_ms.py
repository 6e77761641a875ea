"""Median STAT (HEAD object) latency in the window: SigV4, routing, lock
and the xl.meta quorum read, no erasure work (clients' timings)."""
import window


def read(run):
    return window.latency_ms(run, "STAT", 0.5)
