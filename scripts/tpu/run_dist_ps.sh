#!/usr/bin/env bash
# The multi-process HiPS PS topology on a TPU VM: one OS process per node
# role, exactly scripts/cpu/run_dist_ps.sh.  Every role of the host plane
# — servers AND workers — runs on the CPU (examples/dist_ps.py pins
# jax_platforms=cpu in its workers): a chip belongs to one process at a
# time, so of the processes this script forks none may hold it.  Training
# on the chip is the SPMD plane (one process, scripts/tpu/run_*.sh); this
# script is here so the host plane can be exercised on the same VM.  For
# multi-host deployments use scripts/launch.py with a hostfile
# (docs/deployment.md).
# Reference analogue: scripts/gpu/run_vanilla_hips.sh's process model.
set -euo pipefail
: "${GEOMX_NUM_PARTIES:=2}"
: "${GEOMX_WORKERS_PER_PARTY:=2}"
export GEOMX_NUM_PARTIES GEOMX_WORKERS_PER_PARTY
exec "$(dirname "$0")/../cpu/run_dist_ps.sh" "$@"
