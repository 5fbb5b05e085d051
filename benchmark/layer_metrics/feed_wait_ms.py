"""Time the step loop waited for a placed batch, measured inside the
program: the ``feed_wait`` spans around the consumer's wait in
``utils/prefetch.bounded_prefetch`` that began in the untraced rest of
the window, over its steps. ``input_wait_ms`` times the same wait from
outside, around ``next()`` on the feed."""

import feed_spans


def read(run):
    return feed_spans.ms_per_step(run, "feed_wait")
