#!/usr/bin/env python
"""``lib/client.py`` with a ``prepare`` that takes ``cfg["prepare_s"]``
seconds longer: the client of the test that a window whose bodies take
longer than ``lead_s`` still starts its loops on time."""
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "lib"))
import client  # noqa: E402

_prepare = client.prepare


def prepare(cfg, plans):
    time.sleep(cfg["prepare_s"])
    _prepare(cfg, plans)


client.prepare = prepare

if __name__ == "__main__":
    sys.exit(client.main())
