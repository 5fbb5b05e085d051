"""What the loop waits at an epoch's start, when the feed's worker is
started anew and nothing is prefetched: the ``feed_wait`` spans of
``seq`` 0 to 2 of every epoch whose first wait began in the untraced rest
of the window, over those epochs."""

import feed_spans

FIRST = 3


def read(run):
    spans, _ = feed_spans.in_rest(run, "feed_wait")
    spans = [s for s in spans if not s.get("end") and s["seq"] < FIRST]
    begun = {s["epoch"] for s in spans if s["seq"] == 0}
    if not begun:
        return None
    return 1e3 * feed_spans.seconds(
        s for s in spans if s["epoch"] in begun) / len(begun)
