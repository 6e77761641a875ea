"""The run process's side of the client processes: start them, hand each a
list of per-thread plans, collect the records, stop them and wait."""
from __future__ import annotations

import json
import os
import subprocess
import sys

CLIENT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "client.py")


class ClientPool:
    def __init__(self, n_procs: int, cfg: dict):
        env = dict(os.environ, PYTHONDONTWRITEBYTECODE="1",
                   OMP_NUM_THREADS="1", OPENBLAS_NUM_THREADS="1")
        self.procs = [subprocess.Popen(
            [sys.executable, CLIENT], stdin=subprocess.PIPE,
            stdout=subprocess.PIPE, env=env, text=True)
            for _ in range(n_procs)]
        for p in self.procs:
            self._send(p, {"cmd": "init", "cfg": cfg})
        for p in self.procs:
            self._recv(p)

    @staticmethod
    def _send(p, cmd: dict) -> None:
        p.stdin.write(json.dumps(cmd) + "\n")
        p.stdin.flush()

    @staticmethod
    def _recv(p) -> dict:
        line = p.stdout.readline()
        if not line:
            raise RuntimeError(f"client process {p.pid} ended "
                               f"(exit {p.poll()}) without a reply")
        reply = json.loads(line)
        if not reply.get("ok"):
            raise RuntimeError(f"client process {p.pid}: {reply}")
        return reply

    def _deal(self, cmd: str, plans: list[dict]) -> None:
        """Send ``plans`` (one per thread) under ``cmd``, each to the
        process its ``proc`` names, the rest dealt round-robin."""
        per = [[] for _ in self.procs]
        self._where = []
        for i, plan in enumerate(plans):
            j = plan.pop("proc", i % len(self.procs))
            self._where.append((j, len(per[j])))
            per[j].append(plan)
        self._busy = [j for j, t in enumerate(per) if t]
        for j in self._busy:
            self._send(self.procs[j], {"cmd": cmd, "threads": per[j]})

    def prepare(self, plans: list[dict]) -> None:
        """Hand out plans whose ``t_start`` and ``t_end`` count from a
        start that is not fixed yet, and wait until every process has made
        and hashed its bodies and says so. ``go`` fixes the start."""
        self._deal("prepare", plans)
        for j in self._busy:
            self._recv(self.procs[j])

    def go(self, t_start: float) -> None:
        """Start the prepared plans: their times count from ``t_start``
        (``time.monotonic``, one clock for all processes of a machine)."""
        for j in self._busy:
            self._send(self.procs[j], {"cmd": "go", "t_start": t_start})

    def finish(self) -> list[list[dict]]:
        """Records of every plan dealt last, in the plans' order."""
        replies = {j: self._recv(self.procs[j])["threads"]
                   for j in self._busy}
        return [replies[j][t] for j, t in self._where]

    def run(self, plans: list[dict]) -> list[list[dict]]:
        """Run ``plans`` at once; their records, in the plans' order."""
        self._deal("run", plans)
        return self.finish()

    def close(self) -> None:
        for p in self.procs:
            if p.poll() is None:
                try:
                    self._send(p, {"cmd": "quit"})
                    p.stdin.close()
                except OSError:
                    pass
        for p in self.procs:
            try:
                p.wait(timeout=20)
            except subprocess.TimeoutExpired:
                p.kill()
                p.wait()
