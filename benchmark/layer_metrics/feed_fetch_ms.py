"""The loader's share of ``feed_batch_ms``: the worker's ``fetch`` spans
(pulling the next batch from the loader: cache lookup and ``np.stack``
on a cached mix, decode on a disk mix) that began in the untraced rest of
the window, over its steps."""

import feed_spans


def read(run):
    return feed_spans.ms_per_step(run, "fetch")
