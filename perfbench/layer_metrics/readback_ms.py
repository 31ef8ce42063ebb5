"""readback_ms.<serving mix>: the dispatch boundary, device to host. Per
counted tick, the sum over its ``pb.engine.sync.*`` spans (the read-back of
the program's outputs) of the time from the end of the last device op that
ends inside the enclosing ``pb.engine.dispatch.*`` span to the span's end:
from the program's end until its outputs are on the host, while the chip
waits. Mean over the ticks, in milliseconds; a dispatch with no device op
inside its span is left out (``perfbench/boundary.py``)."""

from perfbench import boundary


def read(res):
    return boundary.per_tick_ms(res, "sync", boundary.readback_ns)
