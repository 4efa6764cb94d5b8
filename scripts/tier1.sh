#!/usr/bin/env bash
# Tier-1 verify (see ROADMAP.md): docs consistency, packed-uplink bench
# smoke, retrieval-engine bench smoke, streaming-aggregation bench smoke,
# physical-channel bench smoke, telemetry bench smoke, mesh-sharding
# bench smoke (all hard-asserted acceptance checks), the forced-8-device
# multidevice lane, then the whole suite, stop on first failure. Run
# from the repo root:
#   bash scripts/tier1.sh [extra pytest args...]
# CI (.github/workflows/ci.yml) runs these same nine commands (and
# uploads the telemetry smoke's TELEMETRY_* artifacts). The PYTHONPATH
# export is belt-and-braces: pytest (conftest.py) and the benches
# (in-file bootstrap) self-locate src/ when invoked standalone. The
# multidevice lane's tests each re-exec in a child interpreter with
# XLA_FLAGS forcing 8 host devices (tests/_multidevice.py), so the
# hosting pytest process keeps its single default device.
set -euo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"
# tier-1 is the CPU lane (interpret-mode kernels, jnp oracles); the
# chip's own check is chip_smoke.py
export JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}"
python scripts/check_docs.py
python benchmarks/bench_aggregation.py --smoke
python benchmarks/bench_retrieval.py --smoke
python benchmarks/bench_streaming.py --smoke
python benchmarks/bench_channel.py --smoke
python benchmarks/bench_obs.py --smoke
python benchmarks/bench_mesh.py --smoke
python -m pytest -q tests/test_distributed.py tests/test_mesh_dataplane.py
python -m pytest -x -q "$@"
