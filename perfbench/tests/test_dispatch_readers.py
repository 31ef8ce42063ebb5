"""The readers of the dispatch boundary and the drive loop's peek (PR 38),
on a neutral trace form written by hand with device ops in it, against
answers worked by hand, in the pattern of ``test_span_readers.py``. Times
below are in milliseconds; the form holds nanoseconds.

Window 100..1100. Engine ticks: 50..150 (cut by the window's start: left
out), 200..400, 420..600, 640..900, 1000..1200 (cut by its end: left out).
Device ops of the first device: 215..230, 230..258 (the prefill of tick
200), 274..300, 300..386 (its decode), 405..410 (in no dispatch), 446..585,
734..870; the second device's ops are another chip's and are not read.

    tick      dispatch (put)              launch -> op  op -> sync end
    200..400  prefill 212..262 (209..211)  213 -> 215: 2  258 -> 260: 2
              decode 270..390 (267..270)   271 -> 274: 3  386 -> 388: 2
    420..600  decode 440..590 (436..440)   441 -> 446: 5  585 -> 589: 4
    640..900  prefill 650..720 (645..648)  no device op: left out
              decode 730..880 (726..729)   731 -> 734: 3  870 -> 879: 9

Per tick: put 5, 4, 6; launch 5, 5, 3; read-back 4, 4, 9. The last tick's
spans lie in no counted tick. Peeks (``pb.server.peek``) 402..403, 603..605,
905..908, and 1099..1101 cut by the window's end.
"""

import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from perfbench import run  # noqa: E402

MS = 1_000_000


def _ns(a, b):
    return [int(a * MS), int(round((b - a) * MS))]


def form_of(spans_ms, ops_ms=()):
    return {"devices": {"/device:TPU:0": [["fusion", *_ns(a, b)]
                                          for a, b in ops_ms],
                        "/device:TPU:1": [["fusion", *_ns(100, 1100)]]},
            "host": [[name, *_ns(a, b)] for name, a, b in spans_ms]}


HARNESS = [("pb.window", 100, 1100), ("pb.step.dispatch", 300, 310)]
# what the parent writes too: ticks, builds and dispatches
PARENT = HARNESS + [
    ("pb.engine.tick", 50, 150), ("pb.engine.dispatch.decode_step", 60, 140),
    ("pb.engine.tick", 200, 400), ("pb.engine.build.prefill", 204, 212),
    ("pb.engine.dispatch.prefill", 212, 262),
    ("pb.engine.build.decode", 262, 270),
    ("pb.engine.dispatch.decode_step", 270, 390),
    ("pb.engine.tick", 420, 600), ("pb.engine.build.decode", 424, 440),
    ("pb.engine.dispatch.decode_step", 440, 590),
    ("pb.engine.tick", 640, 900), ("pb.engine.build.prefill", 642, 650),
    ("pb.engine.dispatch.prefill", 650, 720),
    ("pb.engine.build.decode", 722, 730),
    ("pb.engine.dispatch.decode_step", 730, 880),
    ("pb.engine.tick", 1000, 1200), ("pb.engine.build.decode", 1002, 1010),
    ("pb.engine.dispatch.decode_step", 1010, 1190),
    ("pb.router.progress", 405, 406), ("pb.server.lock_wait", 150, 405),
]
NEW = [
    ("pb.engine.put.prefill", 209, 211), ("pb.engine.launch.prefill", 213, 216),
    ("pb.engine.sync.prefill", 216, 260),
    ("pb.engine.put.decode_step", 267, 270),
    ("pb.engine.launch.decode_step", 271, 275),
    ("pb.engine.sync.decode_step", 275, 388),
    ("pb.engine.put.decode_step", 436, 440),
    ("pb.engine.launch.decode_step", 441, 444),
    ("pb.engine.sync.decode_step", 444, 589),
    ("pb.engine.put.prefill", 645, 648), ("pb.engine.launch.prefill", 651, 652),
    ("pb.engine.sync.prefill", 652, 719),
    ("pb.engine.put.decode_step", 726, 729),
    ("pb.engine.launch.decode_step", 731, 733),
    ("pb.engine.sync.decode_step", 733, 879),
    ("pb.engine.put.decode_step", 1005, 1008),
    ("pb.engine.launch.decode_step", 1011, 1013),
    ("pb.engine.sync.decode_step", 1013, 1189),
    ("pb.server.peek", 402, 403), ("pb.server.peek", 603, 605),
    ("pb.server.peek", 905, 908), ("pb.server.peek", 1099, 1101),
]
OPS = [(215, 230), (230, 258), (274, 300), (300, 386), (405, 410),
       (446, 585), (734, 870), (1012, 1188)]
SERVE = form_of(PARENT + NEW, OPS)
BARE = form_of(PARENT, OPS)  # the parent's trace: ops, but no such span

READERS = {
    "arg_put_ms.backlog": (5 + 4 + 6) / 3,
    "launch_ms.agent-backlog": (5 + 5 + 3) / 3,
    "readback_ms.chat-backlog": (4 + 4 + 9) / 3,
    "peek_ms.code-backlog": (1 + 2 + 3) / 3,
}


@pytest.mark.parametrize("form", ["spans", "bare", "untraced"])
@pytest.mark.parametrize("metric", sorted(READERS))
def test_dispatch_reader(metric, form):
    read = run.load_reader(metric)
    if form == "spans":
        assert read({"trace": SERVE, "facts": {}}) == pytest.approx(
            READERS[metric], rel=1e-9)
    else:  # nothing to read: None, never 0, and no raise
        trace = BARE if form == "bare" else None
        assert read({"trace": trace, "facts": {}}) is None


@pytest.mark.parametrize("metric", ["launch_ms.backlog", "readback_ms.backlog"])
def test_a_dispatch_without_device_ops_is_left_out(metric):
    """A tick whose one dispatch holds no device op gives no interval, and
    a trace without device ops gives no metric: None, not 0."""
    one = [("pb.window", 100, 1100), ("pb.engine.tick", 200, 400),
           ("pb.engine.dispatch.prefill", 210, 300),
           ("pb.engine.launch.prefill", 211, 215),
           ("pb.engine.sync.prefill", 215, 299)]
    read = run.load_reader(metric)
    assert read({"trace": form_of(one, [(305, 390)]), "facts": {}}) is None
    assert read({"trace": {"devices": {}, "host": form_of(one)["host"]},
                 "facts": {}}) is None
    # the tick with an op in its dispatch counts alone
    two = one + [("pb.engine.tick", 420, 600),
                 ("pb.engine.dispatch.decode_step", 430, 590),
                 ("pb.engine.launch.decode_step", 431, 434),
                 ("pb.engine.sync.decode_step", 434, 589)]
    want = {"launch_ms.backlog": 2, "readback_ms.backlog": 9}[metric]
    got = read({"trace": form_of(two, [(305, 390), (433, 580)]), "facts": {}})
    assert got == pytest.approx(want, rel=1e-9)


def test_a_clock_that_disagrees_reads_negative_not_nought():
    """An op that ends after the read-back has returned says the host
    spans and the device ops disagree on the clock: the interval stays
    negative, unclamped."""
    skew = [("pb.window", 100, 1100), ("pb.engine.tick", 200, 400),
            ("pb.engine.dispatch.decode_step", 210, 300),
            ("pb.engine.launch.decode_step", 211, 215),
            ("pb.engine.sync.decode_step", 215, 298)]
    res = {"trace": form_of(skew, [(213, 299.5)]), "facts": {}}
    assert run.load_reader("readback_ms.backlog")(res) == pytest.approx(-1.5)
    assert run.load_reader("launch_ms.backlog")(res) == pytest.approx(2)
