#!/bin/sh
# Runs of one cell as the driver makes them, one result line each:
#   sh perfbench/tools/measure.sh <cell> <seconds> <first seed> <runs> [trace]
# Each run is a new process with another --seed. Lines go to stdout and to
# chiprun_out/measure/<cell>.jsonl for perfbench/tools/spread.py.
cell=$1; seconds=$2; seed=$3; runs=$4; trace=${5:-0}
mkdir -p chiprun_out/measure
i=0
while [ $i -lt $runs ]; do
  python3 perfbench/run.py --workload $cell --seed $((seed + i)) --seconds $seconds --trace $trace \
    > chiprun_out/measure/$cell.last.log 2>&1
  rc=$?
  line=$(tail -n 1 chiprun_out/measure/$cell.last.log)
  if [ $rc -ne 0 ]; then echo "$cell seed $((seed + i)) rc=$rc"; tail -n 30 chiprun_out/measure/$cell.last.log; fi
  echo "$line" | cut -c1-2500
  echo "$line" >> chiprun_out/measure/$cell.trace$trace.jsonl
  grep -h "perfbench: memory peak\|perfbench: initial check\|ms a step by window" chiprun_out/measure/$cell.last.log >> chiprun_out/measure/$cell.notes.log
  i=$((i + 1))
done
