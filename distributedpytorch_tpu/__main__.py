"""``python -m distributedpytorch_tpu`` → the training CLI (same surface
as ``train.py`` / the ``dpt-train`` console script), plus the elastic
supervisor subcommand:

    python -m distributedpytorch_tpu elastic -n 2 -- -t FSDP ...

which spawns/supervises the worker ranks (dist/elastic.py) the way the
reference's ``torchrun`` launcher does (README.md:37), and the static
analyzer:

    python -m distributedpytorch_tpu analyze [--strategies ...]

which runs dptlint (analysis/: jaxpr collective checker + SPMD source
lint; docs/ANALYSIS.md) on a self-provisioned CPU mesh — the CI
``lint-distributed`` gate and the elastic launch preflight call this —
the parallelism auto-planner:

    python -m distributedpytorch_tpu plan --out plan.json

which searches strategy × schedule × memory levers with zero device
execution and emits a ranked plan file (``analyze --plan`` checks it
for staleness; analysis/planner.py, docs/PERFORMANCE.md "Planning") —
its serving twin:

    python -m distributedpytorch_tpu plan-serve --profile profile.json

which replays arrival traces against profiled service times in a
discrete-event simulation of the live queue policy and emits replica
recommendations per (traffic, SLO) with zero devices and zero jax
(analysis/serve_planner.py, docs/SERVING.md "Capacity planning") — and
the serving tier:

    python -m distributedpytorch_tpu serve -c singleGPU --port 8008

AOT-compiled, continuous-batching inference over HTTP (serve/,
docs/SERVING.md) — the inference-side production workload — and its
executable store manager:

    python -m distributedpytorch_tpu aot {warm,ls,gc}

prewarm / inspect / LRU-bound the content-addressed AOT executable
store (utils/aotstore.py, docs/PERFORMANCE.md "AOT executable
store")."""

import sys


def main() -> None:
    if len(sys.argv) > 1 and sys.argv[1] == "elastic":
        from distributedpytorch_tpu.dist.elastic import main as elastic_main

        sys.exit(elastic_main(sys.argv[2:]))
    if len(sys.argv) > 1 and sys.argv[1] == "analyze":
        from distributedpytorch_tpu.analysis.cli import main as analyze_main

        sys.exit(analyze_main(sys.argv[2:]))
    if len(sys.argv) > 1 and sys.argv[1] == "plan":
        from distributedpytorch_tpu.analysis.planner import main as plan_main

        sys.exit(plan_main(sys.argv[2:]))
    if len(sys.argv) > 1 and sys.argv[1] == "plan-serve":
        from distributedpytorch_tpu.analysis.serve_planner import (
            main as plan_serve_main,
        )

        sys.exit(plan_serve_main(sys.argv[2:]))
    if len(sys.argv) > 1 and sys.argv[1] == "serve":
        from distributedpytorch_tpu.serve.cli import main as serve_main

        sys.exit(serve_main(sys.argv[2:]))
    if len(sys.argv) > 1 and sys.argv[1] == "aot":
        from distributedpytorch_tpu.utils.aotstore import main as aot_main

        sys.exit(aot_main(sys.argv[2:]))
    from distributedpytorch_tpu.cli import main as cli_main

    cli_main()


if __name__ == "__main__":
    main()
