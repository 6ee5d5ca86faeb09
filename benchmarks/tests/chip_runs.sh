#!/bin/sh
# Several runs of one cell in one call; result lines under chiprun_out/.
# Usage: chip_runs.sh <tag> <workload> <seconds> <trace> <seed>...
TAG=$1; W=$2; S=$3; T=$4; shift 4
mkdir -p chiprun_out
for SEED in "$@"; do
  python3 benchmarks/run.py --workload $W --seed $SEED --seconds $S --trace $T \
    2> chiprun_out/$TAG.$SEED.err | tail -n 2 >> chiprun_out/$TAG.jsonl
  echo "seed $SEED rc=$?"
  tail -n 1 chiprun_out/$TAG.jsonl | cut -c1-700
done
