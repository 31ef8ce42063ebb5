"""window_positions_useful.code-backlog: of the positions the window group's
pages hold, the share that lies inside some row's window, in percent, over
the nominal window: the engine samples, at every decode dispatch, the
positions its rows' window-group pages hold (``window_positions_held``) and
the positions those rows' windows need (``window_positions_needed``: min(pos
+ 1, sliding_window) a row); the share is the ratio of the two counters'
differences between the two ``/healthz`` bodies. A pool that kept every
page to the row's depth would read 1,024 over the mean depth (some 30%); one
that releases behind the window reads the window over what a row holds of
it: the window, the page that is being left, and during prefill the chunk
being written. A program without these counters (no window group): nothing
to read."""

from perfbench.counters import window_difference


def read(res):
    held = window_difference(res, "window_positions_held")
    needed = window_difference(res, "window_positions_needed")
    if not held or not needed:
        return None
    return 100.0 * needed / held
