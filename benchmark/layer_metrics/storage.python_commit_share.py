"""Share of the window's version commits (``rename_data``) whose
file-system sequence ran step by step from Python, a turn at the interpreter
lock a step, and not as one native call:
minio_tpu_storage_commits_total{route="python"} over all routes, in %. Has
to read 0 over a served window (an armed disk fault is what makes it rise)."""
import counter_edges
from served import say


def read(run):
    commits = counter_edges.moved(run, "minio_tpu_storage_commits_total")
    if not commits or not sum(commits.values()):
        return None
    by_route = {counter_edges.label(k, "route"): v
                for k, v in commits.items() if v}
    say(f"storage.python_commit_share: commits by route {by_route}")
    return 100.0 * by_route.get("python", 0.0) / sum(by_route.values())
