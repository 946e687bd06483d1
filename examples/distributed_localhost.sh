#!/usr/bin/env bash
# The reference's full process topology on localhost: backup + primary +
# two client agents over gRPC (README.md of the reference, its de facto
# integration test), with compressed sparse-delta updates and per-round
# checkpointing. Everything shuts down when the primary finishes.
#
# Four jax processes, and a chip belongs to ONE process: every process is
# pinned with --platform. On a one-chip host pass "tpu" as the first
# argument and the PRIMARY takes the chip (docs/OPERATIONS.md §1 says why);
# the clients and the backup always run on the CPU. Default: all on CPU.
set -euo pipefail
cd "$(dirname "$0")/.."

PRIMARY_PLATFORM="${1:-cpu}"
COMMON="--model mlp --dataset synthetic --num-examples 512 --batch-size 16 --lr 0.05 -c Y"

python -m fedtpu.cli.client --platform cpu -a localhost:50051 $COMMON --seed 1 &
C1=$!
python -m fedtpu.cli.client --platform cpu -a localhost:50052 $COMMON --seed 2 &
C2=$!
python -m fedtpu.cli.server --platform cpu $COMMON --listen localhost:50060 &
B=$!
trap 'kill $C1 $C2 $B 2>/dev/null || true' EXIT

echo "waiting for agents to come up..."
sleep 20

python -m fedtpu.cli.server --platform "$PRIMARY_PLATFORM" --p y $COMMON --rounds 5 \
    --clients localhost:50051,localhost:50052 \
    --backupAddress localhost --backupPort 50060 \
    --checkpoint-dir ./checkpoint/demo
