"""The feed's ``h2d`` spans (``StepTimeline``, recorded by the prefetch
worker around ``strategy.place_work``) that began inside the untraced
rest of the window (the profiler holds the feed back while it runs),
over its steps."""


def read(run):
    w = run["window"].get("untraced")
    if not w or not w["steps"]:
        return None
    spans = [s for s in run["spans"] if s["phase"] == "h2d" and s["t0"] >= w["t0"]]
    if not spans:
        return None
    return 1e3 * sum(s["t1"] - s["t0"] for s in spans) / w["steps"]
