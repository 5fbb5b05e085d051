"""Time the step loop waited for a placed batch (the benchmark's span
around ``next()`` on the feed), summed over the untraced rest of the
window (the profiler holds the feed back while it runs), over its
steps."""


def read(run):
    w = run["window"].get("untraced")
    return 1e3 * w["wait_s"] / w["steps"] if w and w["steps"] else None
