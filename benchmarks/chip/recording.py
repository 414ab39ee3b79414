"""A results store that records every commit the served path makes.

The serving layer commits one version per session at each commit point
(admission, applied updates, ticks).  Installed as ``Server.results``
before the first admission, this subclass of the program's store keeps,
per session, the host time of each commit, what the summary it
committed says and the panel, so the benchmark reads the solve's
progress at the moment each version became readable.
"""
from __future__ import annotations

import threading
import time

from repro.serve.results import VersionedResults


class RecordingResults(VersionedResults):
    def __init__(self):
        super().__init__()
        self._rec_lock = threading.Lock()
        self.commits: dict[str, list[tuple]] = {}

    def commit(self, sid: str, summary: dict, panel) -> int:
        version = super().commit(sid, summary, panel)
        rec = (time.perf_counter(), version, int(summary["num_edges"]),
               bool(summary["converged"]), panel)
        with self._rec_lock:
            self.commits.setdefault(sid, []).append(rec)
        return version

    def history(self, sid: str) -> list[tuple]:
        """[(t, version, num_edges, converged, panel)] in commit
        order."""
        with self._rec_lock:
            return list(self.commits.get(sid, ()))
