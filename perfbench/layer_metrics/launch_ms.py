"""launch_ms.<serving mix>: the dispatch boundary, host to device. Per
counted tick, the sum over its ``pb.engine.launch.*`` spans (the program
call) of the time from the span's start to the start of the first device
op at or after it inside the enclosing ``pb.engine.dispatch.*`` span: the
call plus the runtime's start latency, while the chip waits. Mean over the
ticks, in milliseconds; a dispatch with no device op inside its span is
left out (``perfbench/boundary.py``)."""

from perfbench import boundary


def read(res):
    return boundary.per_tick_ms(res, "launch", boundary.launch_ns)
