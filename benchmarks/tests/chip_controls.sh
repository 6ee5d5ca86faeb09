#!/bin/sh
# A cell's control at the cell's own size: a run and, in the same process,
# the control's comparison, per seed; the first three seeds traced.
# Usage: chip_controls.sh <tag> <workload> <seconds> <seed>...
TAG=$1; W=$2; S=$3; shift 3
mkdir -p chiprun_out
N=0
for SEED in "$@"; do
  T=0; [ $N -lt 3 ] && T=1; N=$((N + 1))
  python3 benchmarks/tests/control.py --workload $W --seed $SEED --seconds $S --trace $T \
    2> chiprun_out/$TAG.$SEED.err | tail -n 1 >> chiprun_out/$TAG.jsonl
  echo "seed $SEED trace $T rc=$?"
  tail -n 1 chiprun_out/$TAG.jsonl | cut -c1-900
done
