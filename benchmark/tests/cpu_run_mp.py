#!/usr/bin/env python
"""``cpu_run.py`` with the breaks of the multipart kinds added to its
``--break`` table: the served path broken underneath the harness in ways
that every round trip survives and only the kind's own checks can see.

    JAX_PLATFORMS=cpu python cpu_run_mp.py <checkout> <run.py's arguments>
                    [--break plain-part|wrong-part-range|resident]"""
from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import cpu_run  # noqa: E402


def break_plain_part() -> None:
    """Every package is "sealed" as itself plus 16 zero bytes and "opened"
    by dropping them: sizes, ETags and read-backs all fit, and the body is
    on the drives in plaintext."""
    from minio_tpu.crypto import sse

    class Identity:
        name = sse.CIPHER_AESGCM

        def seal_block(self, seq0, pkgs):
            return [bytes(p) + bytes(sse.TAG) for p in pkgs]

        def open_block(self, seq0, cts):
            return [bytes(c)[:-sse.TAG] for c in cts]
    sse.package_cipher = lambda cipher, oek, base_iv: Identity()


def break_wrong_part_range() -> None:
    """A ranged read that starts after the first part is answered with the
    same bytes of the part before it."""
    from minio_tpu.crypto import sse
    orig = sse.plan_range

    def plan_range(streams, offset, length):
        if len(streams) > 1 and offset >= streams[0].plain and length > 0 \
                and offset + length < sum(s.plain for s in streams):
            offset -= streams[0].plain
        return orig(streams, offset, length)
    sse.plan_range = plan_range
    import minio_tpu.crypto as crypto
    crypto.plan_range = plan_range


def break_resident() -> None:
    """The part handler reads a part's whole body into memory before it
    seals the first package, and a GET under SSE keeps the object's
    plaintext until its last package is open: every answer is right, and
    a part, then the object, is resident."""
    import io

    from minio_tpu.crypto import sse
    seal_init = sse.EncryptReader.__init__
    open_init = sse.RangeDecryptWriter.__init__
    open_finish = sse.RangeDecryptWriter.finish

    class Hold:
        def __init__(self, writer):
            self.writer, self.kept = writer, []

        def write(self, b):
            self.kept.append(bytes(b))
            return len(b)

    def init_seal(self, stream, *a, **kw):
        seal_init(self, io.BytesIO(stream.read()), *a, **kw)

    def init_open(self, writer, *a, **kw):
        open_init(self, Hold(writer), *a, **kw)

    def finish(self):
        open_finish(self)
        hold = self.writer
        for b in hold.kept:
            hold.writer.write(b)
        hold.kept = []
    sse.EncryptReader.__init__ = init_seal
    sse.RangeDecryptWriter.__init__ = init_open
    sse.RangeDecryptWriter.finish = finish


cpu_run.BREAKS.update({"plain-part": break_plain_part,
                       "wrong-part-range": break_wrong_part_range,
                       "resident": break_resident})


def main() -> int:
    """``cpu_run.main`` after the configuration's ``env`` is set, as
    ``run.main`` does (this kind's configuration has one)."""
    root = os.path.abspath(sys.argv[1])
    sys.path.insert(0, os.path.join(root, "benchmark"))
    import run
    args, _ = run.arg_parser().parse_known_args(sys.argv[2:])
    bench = run.load_json(run.ROOT, "BENCHMARK.json")
    os.environ.update(run.resolve(bench, args.workload)[1].get("env", {}))
    return cpu_run.main()


if __name__ == "__main__":
    sys.exit(main())
