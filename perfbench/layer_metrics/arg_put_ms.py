"""arg_put_ms.<serving mix>: the engine tick's host-to-device transfers of
the staged arguments. Mean over the ``pb.engine.tick`` spans inside the
traced window of the summed ``pb.engine.put.*`` spans they hold (the tuple
of ``jnp.asarray`` calls inside ``pb.engine.build.*``), in milliseconds: a
part of ``tick_host_ms``, while the chip waits."""

from perfbench import spans


def read(res):
    form = res.get("trace")
    ticks = spans.spans_of(form, "pb.engine.tick")
    puts = spans.within(spans.spans_of(form, "pb.engine.put."), ticks)
    if not ticks or not puts:
        return None
    return sum(e - s for s, e in puts) / len(ticks) / 1e6
