"""What the prefetch worker spends on one batch: its ``fetch`` (the
loader's batch assembly), ``stack`` and ``h2d`` (the enqueue of the copy)
spans that began in the untraced rest of the window, over its steps. Not
``slot_wait``: this is the least period at which the feed can hand over
a batch, and against ``device_step_ms`` it says which of the two sets
``train_imgs_per_s``."""

import feed_spans


def read(run):
    return feed_spans.ms_per_step(run, "fetch", "stack", "h2d")
