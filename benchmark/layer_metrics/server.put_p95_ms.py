"""95th percentile of PUT latency over every PUT of the window, as
e2e_metrics/put_p95_ms.py reads it (first byte sent to the 200 at write
quorum, clients' timings), for the cells in which that number's runs spread
too widely to be held to a bound: the same number, reported and not judged."""
import window


def read(run):
    return window.latency_ms(run, "PUT", 0.95)
