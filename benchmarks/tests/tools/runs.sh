#!/bin/bash
# Several runs of one cell in one chip call, each a process of its own.
# usage: runs.sh <tag> <cell> <seconds> <trace> <seed>...   -> chiprun_out/<tag>/<seed>.{out,err}, summary lines
tag=$1; cell=$2; secs=$3; trace=$4; shift 4
mkdir -p chiprun_out/$tag
for seed in "$@"; do
  t0=$SECONDS
  python3 benchmarks/run.py --workload $cell --seed $seed --seconds $secs --trace $trace > chiprun_out/$tag/$seed.out 2> chiprun_out/$tag/$seed.err
  rc=$?
    echo "RUN $tag $cell seed=$seed trace=$trace rc=$rc wall=$((SECONDS - t0))s $(tail -n 1 chiprun_out/$tag/$seed.out | cut -c1-1400)"
  grep "set-up " chiprun_out/$tag/$seed.err | tail -1
done
