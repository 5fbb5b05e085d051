"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one cell, one configuration, one traffic mix
or one per-layer metric is a file of its own, found by the name that
``BENCHMARK.json`` gives it:

    benchmark/workloads/<cell>.json        the cell: kind, TrainConfig overrides, limits
    benchmark/configs/<config>.json        the configuration's sizes
    benchmark/references/<reference>.py    its plain reference and the walk of its convolutions
    benchmark/traffic/<traffic>.json       the traffic mix's parameters
    benchmark/drivers/<kind>.py            what drives a cell of that kind
    benchmark/layer_metrics/<metric>.py    one reader per per-layer metric; an entry
                                           named <reader>.<suffix> that has no file
                                           of its own is read by <reader>.py

The last line of standard output is the result's JSON object. Without a
TPU, with fewer chips than the cell asks for, with a device kind that
``peaks.json`` does not know, or without the program beside it, it
prints a message and exits with another code than 0. ``--rehearse`` is
the harness's own mode for the CPU: a tiny size, every device metric left
out as not measured; no cell's command uses it.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if HERE not in sys.path:
    sys.path.insert(0, HERE)
if ROOT not in sys.path:
    sys.path.insert(1, ROOT)

#: Seconds of the window that a ``--trace 1`` run traces (its first).
TRACE_SECONDS = 4.0
#: What ``--rehearse`` shrinks: sizes only, never a width.
REHEARSAL = {
    "image_size": [96, 64],
    "batch_per_chip": 2,
    "samples_per_chip": 12,
}


def fail(message: str, code: int = 2):
    print("benchmark: " + message, file=sys.stderr)
    sys.exit(code)


def load_json(*parts):
    path = os.path.join(*parts)
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        fail(f"{os.path.relpath(path, ROOT)} is missing")


def load_module(kind_dir: str, name: str):
    path = os.path.join(HERE, kind_dir, name + ".py")
    if not os.path.exists(path):
        return None
    spec = importlib.util.spec_from_file_location(f"bench_{kind_dir}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(metric: str):
    """The reader of a per-layer metric: ``layer_metrics/<metric>.py``, or,
    for an entry named ``<reader>.<suffix>`` with no file of its whole
    name, ``layer_metrics/<reader>.py``: one reader serves several
    entries, each with a ``workloads`` list of its own."""
    parts = metric.split(".")
    for n in range(len(parts), 0, -1):
        reader = load_module("layer_metrics", ".".join(parts[:n]))
        if reader is not None:
            return reader
    return None


class Context:
    def __init__(self, args, bench, cell, config, devices, peak):
        self.args, self.bench, self.cell, self.config = args, bench, cell, config
        self.devices, self.peak = devices, peak
        self.root, self.t_start = ROOT, T_START
        self.trace_seconds = TRACE_SECONDS

    def say(self, text: str):
        print(text, file=sys.stderr, flush=True)

    def rehearsal_train_config(self) -> dict:
        if not self.args.rehearse:
            return {}
        return {"image_size": REHEARSAL["image_size"],
                "batch_size": REHEARSAL["batch_per_chip"] * self.cell["chips"],
                "host_cache_mb": 64}

    def rehearsal_traffic(self) -> dict:
        if not self.args.rehearse:
            return {}
        return {"samples": REHEARSAL["samples_per_chip"] * self.cell["chips"]}

    def effective_config(self) -> dict:
        if not self.args.rehearse:
            return self.config
        return {**self.config, "image_size": REHEARSAL["image_size"]}


def applies(metric: dict, cell_name: str) -> bool:
    return "workloads" not in metric or cell_name in metric["workloads"]


def parser():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal at a tiny size; measures nothing")
    ap.add_argument("--keep-trace", action="store_true")
    ap.add_argument("--fault", default=None,
                    help="break the timed path underneath (tests; --rehearse only)")
    return ap


def open_cell(args):
    """(context, driver) of the cell ``args.workload`` names: its files
    read, the program found, jax's compile cache fixed, the devices
    counted and looked up in ``peaks.json``. Exits where a run could
    measure nothing."""
    if args.fault and not args.rehearse:
        fail("--fault is for the rehearsal's tests alone")
    bench = load_json(ROOT, "BENCHMARK.json")
    entry = next((w for w in bench["workloads"] if w["name"] == args.workload), None)
    if entry is None:
        fail(f"BENCHMARK.json has no workload {args.workload!r}")
    cell = {**load_json(HERE, "workloads", entry["name"] + ".json"),
            "name": entry["name"], "config": entry["config"],
            "traffic": entry["traffic"], "chips": entry["chips"]}
    config = load_json(HERE, "configs", entry["config"] + ".json")
    peaks = load_json(HERE, "peaks.json")
    driver = load_module("drivers", cell["kind"])
    if driver is None:
        fail(f"no driver benchmark/drivers/{cell['kind']}.py for kind {cell['kind']!r}")
    try:
        import distributedpytorch_tpu  # noqa: F401
    except ImportError as exc:
        fail(f"the program is not beside the benchmark: {exc}")

    if args.rehearse:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        os.environ.setdefault(
            "XLA_FLAGS", f"--xla_force_host_platform_device_count={cell['chips']}")
    import jax

    # the persistent compile cache: where the environment says, else at one
    # fixed place inside the checkout; every program is kept, however small
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

    try:
        devices = jax.devices()
    except RuntimeError as exc:
        fail(f"jax found no device: {exc}")
    platform = devices[0].platform
    if platform != "tpu" and not (args.rehearse and platform == "cpu"):
        fail(f"jax runs on {platform!r}, not on a TPU; nothing is measured "
             "(--rehearse is the CPU rehearsal)")
    if len(devices) < cell["chips"]:
        fail(f"the cell asks for {cell['chips']} chips and jax has {len(devices)}")
    kind = devices[0].device_kind
    peak = peaks.get(kind)
    if peak is None and not args.rehearse:
        fail(f"device kind {kind!r} is not in benchmark/peaks.json "
             f"(known: {sorted(k for k in peaks if not k.startswith('_'))})")
    return Context(args, bench, cell, config, devices[: cell["chips"]], peak), driver


def main(argv=None) -> int:
    args = parser().parse_args(argv)
    ctx, driver = open_cell(args)
    bench, cell, peak, devices = ctx.bench, ctx.cell, ctx.peak, ctx.devices
    platform, kind = devices[0].platform, devices[0].device_kind

    try:
        run = driver.run(ctx)
    except getattr(driver, "NotMeasurable", ()) as exc:
        fail(exc.message, exc.code or 2)

    # --- metrics -----------------------------------------------------------
    run.update(ctx=ctx, cell=cell, config=ctx.effective_config(), peak=peak,
               rehearsal=bool(args.rehearse))
    trace = None
    if args.trace and run.get("trace_dir"):
        import trace_reduce
        try:
            trace = trace_reduce.load(trace_reduce.newest_xplane(run["trace_dir"]))
        finally:
            if not args.keep_trace:
                shutil.rmtree(run["trace_dir"], ignore_errors=True)
    run["trace"] = trace
    metrics = {}
    if args.trace:
        for m in bench["per_layer"]:
            if not applies(m, cell["name"]):
                continue
            reader = load_reader(m["name"])
            if reader is None:
                fail(f"no reader benchmark/layer_metrics/{m['name']}.py")
            value = reader.read(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
            else:
                ctx.say(f"{m['name']}: not measured")
    else:
        for m in bench["end_to_end"]:
            if applies(m, cell["name"]):
                metrics[m["name"]] = {"value": run["end_to_end"][m["name"]],
                                      "unit": m["unit"]}

    device = {"platform": platform, "kind": kind, "count": len(devices),
              "memory_peak_bytes": run["peak_bytes"]}
    out = {"correct": bool(run["verdict"]["correct"]),
           "attempted": run["attempted"], "failed": run["failed"],
           "metrics": metrics, "device": device}
    if args.trace and trace is not None and trace["devices"]:
        summary = driver.trace_summary(run)
        device.update(busy_s=summary["busy_s"], window_s=summary["window_s"])
        out["breakdown"] = summary["breakdown"]
    if args.rehearse:
        out["rehearsal"] = "CPU at a tiny size: no number here is a measurement"
    out["info"] = run.get("info", {})
    out["checks"] = {name: {"value": value, "limit": limit}
                     for name, value, limit in run["verdict"]["rows"]}
    for name, value, limit in run["verdict"]["rows"]:
        ctx.say(f"check {name}: {value!r} (limit {limit!r})")
    ctx.say(f"correct: {out['correct']}")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
