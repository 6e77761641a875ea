"""Ids that are names, not secrets: span and trace ids, the names of
staging entries and of data directories.

They are minted in the process, from one private generator seeded once
with 256 bits of the system's entropy (and again in a forked child):
``getrandbits`` is one C call under the interpreter lock, where
``uuid.uuid4()`` is ``os.urandom``, a system call that lets go of the
lock and queues for it again behind every other thread. What the
callers need of these ids is that they do not repeat (64 random bits
inside one trace, 122 inside one directory), never that they cannot be
guessed. Anything handed to a client and kept, or secret (version ids,
upload ids, keys, nonces, credentials), stays on ``os.urandom``.

Not the ``random`` module's shared generator: a test or a library may
seed that one, and two processes would then mint the same names.
"""
from __future__ import annotations

import os
import random
import uuid

_rng = random.Random(os.urandom(32))
os.register_at_fork(after_in_child=lambda: _rng.seed(os.urandom(32)))


def span_id() -> str:
    """16 hex characters."""
    return "%016x" % _rng.getrandbits(64)


def trace_id() -> str:
    """32 hex characters."""
    return "%032x" % _rng.getrandbits(128)


def uuid4_str() -> str:
    """The 36 characters of a version-4 UUID, as ``str(uuid.uuid4())``
    gives them."""
    return str(uuid.UUID(int=_rng.getrandbits(128), version=4))
