"""From the enqueue of a batch's host-to-device copy until its arrays
are ready on the device: the ``h2d_ready`` spans (the program's watcher
thread; each starts where the batch's ``h2d`` span ends) that began in
the untraced rest of the window, over its steps."""

import feed_spans


def read(run):
    return feed_spans.ms_per_step(run, "h2d_ready")
