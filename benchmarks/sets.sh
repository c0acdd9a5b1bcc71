#!/bin/bash
# Several runs of one cell in one call, one JSON line a run:
#
#   chiprun -- bash benchmarks/sets.sh <tag> <cell> <seconds> <trace> <seed>...
#
# appends {"tag", "seed", "rc", "result": <the run's last line>} to
# chiprun_out/<tag>.jsonl and keeps the end of a failed run's log beside
# it.  How PR 24's two sets of six runs a cell were made (PERF.md, 2).
tag=$1; cell=$2; seconds=$3; trace=$4; shift 4
here=$(dirname "$0")
mkdir -p chiprun_out
for seed in "$@"; do
  python3 "$here/run.py" --workload "$cell" --seed "$seed" \
    --seconds "$seconds" --trace "$trace" \
    > chiprun_out/_last.out 2> chiprun_out/_last.err
  rc=$?
  last=$(tail -n 1 chiprun_out/_last.out | grep '^{' || echo null)
  echo "{\"tag\":\"$tag\",\"seed\":$seed,\"rc\":$rc,\"result\":$last}" \
    >> "chiprun_out/$tag.jsonl"
  if [ $rc -ne 0 ]; then
    tail -n 40 chiprun_out/_last.err > "chiprun_out/$tag.$seed.err"
  fi
done
