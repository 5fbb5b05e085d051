"""Time the prefetch worker waited for a free slot (``slot_wait`` spans
that began in the untraced rest of the window), over its steps. Above 0
the feed runs ahead of the loop; near 0 with ``feed_wait_ms`` high the
feed is the bottleneck. A worker that never waited records no such span:
that reads 0 where its ``fetch`` spans are there, and nothing where they
are not (a program without these spans)."""

import feed_spans


def read(run):
    if feed_spans.ms_per_step(run, "fetch") is None:
        return None
    return feed_spans.ms_per_step(run, "slot_wait") or 0.0
