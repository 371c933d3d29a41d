#!/bin/bash
# Parent against change on the same chip, in one call, each from an archive of
# its committed files (README.md, "To re-check"): _parent/ holds the parent
# commit with this tree's benchmark files laid over it, as the driver lays them
# (a reader of a new metric finds nothing there and says nothing), _tree/ the
# change. Writes inside the checkout only (chiprun_out/<tag>/).
#
#   git add -A && rm -rf _tree _parent && mkdir _tree _parent
#   git archive $(git write-tree) | tar -x -C _tree
#   git archive <parent commit> | tar -x -C _parent
#   cp -r BENCHMARK.json _parent/ && cp -r benchmarks/. _parent/benchmarks/
#   chiprun --timeout 3600 -- bash benchmarks/tools/pair.sh <tag> <cell> <seconds> <side>:<seed>[:<flags>] ...
#
# side: p (parent) or c (change). flags: t = --trace 1; k = keep the side's
# _work/ from an earlier run of this call (without it the side's first run of
# the call starts with _work/ empty, as a checkout's first run). Give the runs
# in the order parent, change, change, parent. A summary line a run; the
# cover's costs and every error are echoed.
set -u
tag=$1; cell=$2; secs=$3; shift 3
top=$(pwd); out=$top/chiprun_out/$tag; mkdir -p "$out"
declare -A seen
for spec in "$@"; do
  IFS=: read -r side seed flags <<< "$spec"
  case $side in p) dir=$top/_parent ;; c) dir=$top/_tree ;; *) echo "side? $spec"; exit 2 ;; esac
  cd "$dir" || exit 2
  if [ -z "${seen[$side]:-}" ] && [[ ${flags:-} != *k* ]]; then rm -rf benchmarks/_work; fi
  seen[$side]=1
  trace=0
  [[ ${flags:-} == *t* ]] && trace=1
  name=$side-$seed-t$trace
  t0=$(date +%s.%N)
  python3 benchmarks/run.py --workload "$cell" --seed "$seed" --seconds "$secs" --trace $trace \
      > "$out/$name.out" 2> "$out/$name.err"
  rc=$?
  echo "{\"run\": \"$name\", \"side\": \"$side\", \"seed\": $seed, \"trace\": $trace, \"rc\": $rc, \"wall_s\": $(python3 -c "import time,sys; print(round(time.time()-float(sys.argv[1]),1))" "$t0"), \"line\": $(tail -n 1 "$out/$name.out" | grep '^{' || echo null)}" | tee -a "$out/summary.jsonl"
  grep -E '"phase": "(cover|lead_in)"' "$out/$name.err" | cut -c1-700
  grep -E '^compared|STALL|Traceback|Error|cover_new_key' "$out/$name.err" | tail -n 12
done
ls "${JAX_COMPILATION_CACHE_DIR:-$top/_tree/benchmarks/_work/xla_cache}" 2>/dev/null | wc -l
du -sm "${JAX_COMPILATION_CACHE_DIR:-$top/_tree/benchmarks/_work/xla_cache}" 2>/dev/null
