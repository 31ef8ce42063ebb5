"""peek_ms.<serving mix>: the server's drive loop. Mean duration of the
``pb.server.peek`` spans inside the traced window, in milliseconds: once a
drive tick, under the router lock, every open stream's new tokens read
(a part of ``tick_gap_ms``)."""

from perfbench import spans


def read(res):
    return spans.mean_duration_ms(res, "pb.server.peek")
