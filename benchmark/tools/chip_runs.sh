#!/bin/sh
# Runs of cells on the chip, one process after another, each run's last
# stdout line and the end of its stderr kept under chiprun_out/<tag>/.
#   sh benchmark/tools/chip_runs.sh <tag> <seconds> <trace> <workload> <seed> [<seed> ...]
tag=$1; seconds=$2; trace=$3; workload=$4; shift 4
out=chiprun_out/$tag
mkdir -p "$out"
for seed in "$@"; do
  t0=$(date +%s)
  python3 benchmark/run.py --workload "$workload" --seed "$seed" --seconds "$seconds" --trace "$trace" $BENCH_EXTRA \
    > "$out/$workload.$seed.t$trace.out" 2> "$out/$workload.$seed.t$trace.err"
  rc=$?
  t1=$(date +%s)
  echo "rc=$rc wall=$((t1 - t0))s $workload seed=$seed trace=$trace"
  tail -n 1 "$out/$workload.$seed.t$trace.out" | cut -c1-6000
  grep -E "^(compared|correct|refused)" "$out/$workload.$seed.t$trace.err" | tail -n 12
  tail -c 3000 "$out/$workload.$seed.t$trace.err" > "$out/$workload.$seed.t$trace.errtail"
  rm -f "$out/$workload.$seed.t$trace.err"
done
