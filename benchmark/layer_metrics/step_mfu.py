"""The whole step's share of the chip's peak: logical forward and
backward FLOPs of the configuration's convolutions (``flops.py``) times
the images completed, over the seconds, over chips times the bf16 peak of
``peaks.json``. Taken over the untraced rest of the window: the profiler
holds the feed back while it runs (PERF.md), and a share taken over the
traced seconds too would carry that."""


def read(run):
    w = run["window"].get("untraced")
    if run["rehearsal"] or not run["peak"] or not w or w["steps"] < 1:
        return None
    rate = run["train_flops_per_image"] * w["images"] / w["seconds"]
    return 100.0 * rate / (run["chips"] * run["peak"]["bf16_flops"])
