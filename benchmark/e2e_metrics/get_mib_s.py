"""GET body bytes read to their end inside the window, over the window's
seconds (every body is hashed and compared; a wrong one fails the run)."""
import window


def read(run):
    return window.done_bytes(run, "GET") / window.MIB \
        / run["window"]["seconds"]
