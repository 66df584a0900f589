#!/bin/sh
# Runs of one cell, one after another, in one call on the chip:
#   sh chipbench/measure.sh <cell> <seconds> <seed>:<trace> [<seed>:<trace> ...]
# Each run's output goes to chiprun_out/logs/; its info and result lines
# are echoed.  Stops at the first run that fails, so that a fault costs
# one run and not the call, and starts no run that could not end before
# $CHIPBENCH_DEADLINE (epoch seconds), if that is set.
cell=$1; seconds=$2; shift 2
mkdir -p chiprun_out/logs
for run in "$@"; do
  seed=${run%%:*}; trace=${run##*:}
  start=$(date +%s)
  if [ -n "$CHIPBENCH_DEADLINE" ] && [ $(( start + 420 )) -gt "$CHIPBENCH_DEADLINE" ]; then
    echo "skipped $cell seed=$seed trace=$trace: the call's deadline is near"; continue
  fi
  log=chiprun_out/logs/$cell.$seed.$trace.$(date +%H%M%S)
  timeout 1300 python3 chipbench/run.py --workload "$cell" --seed "$seed" \
      --seconds "$seconds" --trace "$trace" >"$log.out" 2>"$log.err"
  rc=$?
  echo "rc=$rc $cell seed=$seed trace=$trace took $(( $(date +%s) - start ))s"
  tail -n 2 "$log.out" | cut -c1-6000
  if [ $rc -ne 0 ]; then tail -n 30 "$log.err"; exit $rc; fi
done
