"""The stall watcher finds where the generator's thread stood blocked, and
lets its sleeps pass."""
from __future__ import annotations

import threading
import time

from bench import stalls


def send(block: threading.Event, release: threading.Event):
    """Stands in for the generator: sleeps, then blocks in a call below it."""
    time.sleep(0.3)
    block.wait()
    inside(release)
    time.sleep(0.3)


def inside(release: threading.Event):
    release.wait(2.0)


def test_watcher_reports_a_block_and_not_a_sleep():
    block, release = threading.Event(), threading.Event()
    worker = threading.Thread(target=send, args=(block, release))
    worker.start()
    w = stalls.Watcher(worker.ident)
    w.start()
    block.set()
    time.sleep(0.9)  # the worker sleeps 0.3 s, then blocks for about 0.6 s
    release.set()
    worker.join()
    w.stop.set()
    w.join()
    assert len(w.blocked) == 1, w.blocked
    assert 0.4 < w.blocked[0]["s"] < 1.5
    assert any(f.endswith(" inside") for f in w.blocked[0]["where"]), w.blocked
