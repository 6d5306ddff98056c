#!/usr/bin/env bash
# Multi-host launcher (reference parity: cross_silo/hierarchical/
# dist_trainer_launcher.py:23 uses pdsh + torchrun; on TPU pods the
# coordination service replaces the rendezvous backend).
#
# Usage:
#   ./launch_multihost.sh <coordinator_ip:port> <num_hosts> <host_id> <entry.py> [args...]
#
# Each host of a pod slice runs this with its own host_id (0..num_hosts-1);
# fedml_tpu.init() picks the env vars up via
# parallel/mesh.py:maybe_initialize_distributed -> jax.distributed.initialize.
#
# ONE PROCESS PER HOST. A JAX process claims every TPU chip of its host, and
# a chip belongs to one process: a second copy of this on the same host
# fails or hangs at backend start-up. One process already drives all the
# chips of a host. Several processes on one box are a CPU-only arrangement
# (JAX_PLATFORMS=cpu with virtual devices, as the tests do); this launcher
# has not been run on a multi-host TPU slice.
set -euo pipefail

COORD=${1:?coordinator ip:port}
NUM=${2:?num hosts}
ID=${3:?host id}
ENTRY=${4:?entry script}
shift 4

export JAX_COORDINATOR_ADDRESS="$COORD"
export JAX_NUM_PROCESSES="$NUM"
export JAX_PROCESS_ID="$ID"

exec python "$ENTRY" "$@"
