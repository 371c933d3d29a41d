#!/bin/bash
# Trial runs of one cell in the tree as it stands (not the archive), in one
# chip call: chiprun -- bash benchmarks/tools/trial.sh <tag> <cell> <seconds> <seed>[:t] ...
# A seed with ":t" runs with --trace 1; with ":c" through tools/control.py.
# Results: chiprun_out/<tag>/<seed>.out|.err, and a summary line a run.
set -u
tag=$1; cell=$2; secs=$3; shift 3
out=chiprun_out/$tag; mkdir -p "$out"
for s in "$@"; do
  seed=${s%%:*}; trace=0; prog=benchmarks/run.py
  case $s in *:t) trace=1 ;; *:c) prog=benchmarks/tools/control.py ;; esac
  t0=$(date +%s.%N)
  python3 $prog --workload "$cell" --seed "$seed" --seconds "$secs" --trace $trace \
      > "$out/$seed.out" 2> "$out/$seed.err"
  rc=$?
  echo "{\"seed\": \"$s\", \"rc\": $rc, \"wall_s\": $(python3 -c "import time,sys; print(round(time.time()-float(sys.argv[1]),1))" $t0), \"line\": $(tail -n 1 "$out/$seed.out" | grep '^{' || echo null)}" | tee -a "$out/summary.jsonl"
  grep -v heartbeat "$out/$seed.err" | grep '^{' | cut -c1-900 | tail -n 40
  grep -E '^compared|STALL|Traceback|Error' "$out/$seed.err" | tail -n 12
done
ls -la benchmarks/_work/xla_cache 2>/dev/null | awk '{print $5, $9}' | sort -k2 > "$out/cache.txt"
du -sm benchmarks/_work
