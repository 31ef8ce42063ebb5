"""expert_rows_useful.<serving mix>: of the rows the DECODE program ran its
routed-expert products over, the share that were pairs (token, expert)
routed to an expert held here, in percent, over the nominal window: the
difference of the engine's counters ``moe_pairs_here.decode_step`` and
``moe_rows_computed.decode_step`` between the two ``/healthz`` bodies. A
product run over every token for every held expert reads about 2% (8/384);
one over blocks of sorted pairs reads the share of its blocks that is
filled. A program without these counters (no expert layer that counts):
nothing to read."""

from perfbench.counters import window_difference


def read(res):
    pairs = window_difference(res, "moe_pairs_here.decode_step")
    rows = window_difference(res, "moe_rows_computed.decode_step")
    if not pairs or not rows:
        return None
    return 100.0 * pairs / rows
