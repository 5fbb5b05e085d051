#!/usr/bin/env python3
"""Per-kernel compile-only Mosaic accept/reject probes → the per-chip
priors file.

Thirty seconds to learn compiled-or-rejected before a run spends its
budget, as a registry: every Pallas kernel in ``ops/kernels.PROBES`` is AOT-lowered and compiled at a
representative shape — ZERO execution — and the verdicts land in one
versioned priors file that

* ``ops/kernels.get_kernel_policy`` consumes at engagement time ONLY
  where the operator hands it over (``--kernel-priors`` /
  ``$DPT_KERNEL_PRIORS``): a kernel the file marks rejected then
  disengages loudly. With no priors given, a requested kernel that
  Mosaic refuses fails the run at compile time — nothing probes and
  falls back behind the operator's back;
* ``python -m distributedpytorch_tpu plan --kernel-priors`` consumes as
  the ``kernels`` search axis: Mosaic-rejected kernel points are
  rejected with the probe's reason at zero device time.

On a TPU the probes exercise real Mosaic lowering (the verdicts are the
chip's); on an operator-named CPU (``JAX_PLATFORMS=cpu``) the
interpreter path compiles, which proves the machinery but records the
PLANNING backend's verdict — the file stamps ``platform`` so consumers
can tell. The exit code is non-zero when any probed kernel was refused.

Usage:

    python tools/probe_kernels.py [--out kernel_priors.json]
        [--kernels fused_loss conv_epilogue ...]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time


def run_and_save(out_path: str, names=None, emit=None) -> dict:
    """Run the (selected) probe registry and atomically write the priors
    file; returns the payload plus a tiny summary row for bench ledgers."""
    from distributedpytorch_tpu.ops.kernels import run_probes, save_priors

    t0 = time.monotonic()
    payload = run_probes(names=names, emit=emit)
    save_priors(payload, out_path)
    kernels = payload["kernels"]
    rejected = sorted(k for k, v in kernels.items() if not v.get("accepted"))
    return {
        "kind": "kernel_probe",
        "priors_path": os.path.abspath(out_path),
        "platform": payload["platform"],
        "probed": sorted(kernels),
        "rejected": rejected,
        "duration_s": round(time.monotonic() - t0, 2),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Compile-only Mosaic accept/reject probes for every "
                    "Pallas kernel; writes the per-chip priors file "
                    "(ops/kernels.py, docs/PERFORMANCE.md 'Kernels')")
    ap.add_argument("--out", default="kernel_priors.json",
                    help="Priors file to write (versioned JSON)")
    ap.add_argument("--kernels", nargs="+", default=None,
                    help="Probe only these registry kernels "
                         "(default: all)")
    args = ap.parse_args(argv)

    sys.path.insert(
        0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

    def emit(row):
        print(json.dumps(row))

    summary = run_and_save(args.out, names=args.kernels, emit=emit)
    print(json.dumps(summary))
    # the file records every verdict either way; a refusal is also this
    # run's exit code, so it is never just a line in a log
    return 1 if summary["rejected"] else 0


if __name__ == "__main__":
    sys.exit(main())
