#!/bin/bash
# Prove a cell as the driver checks it: from an archive of the committed files
# (unpacked into _tree/ where git is: see README.md), _work/ empty, seeds used
# in no earlier run. Writes inside the checkout only (chiprun_out/proof-<cell>/).
#
#   chiprun --timeout 3600 -- bash benchmarks/tools/cold_proof.sh <cell> <seconds> <seed0> <stage>...
#
# Stages, in the order given:
#   first        the cell's first run in the checkout (no cover file: it plans
#                the wide list; with an empty compile cache it compiles) --
#                recorded apart
#   sets:<n>     set A on n new seeds, then set B on the same seeds (kept
#                corpora), as the check's second set is
#   short:<n>    n further seeds at 10 s, for the dozen
#   runs:<n>:<s> n further seeds with a window of s seconds (a longer window's spread)
#   control:<n>  n further seeds at 10 s through tools/control.py (a run that
#                also reads the control on its own sample)
#   trace:<n>    n further seeds with --trace 1
#   starved      a new seed with the host held to a third of its cores
#   coldvariant  a new seed with the compile-cache entry of one _direct_cube
#                variant removed (the one the last run loaded first)
set -u
cell=$1; secs=$2; seed0=$3; shift 3
top=$(pwd); out=$top/chiprun_out/proof-$cell; mkdir -p "$out"
cd "$top/_tree" || exit 2
rm -rf benchmarks/_work
next=$seed0; last=""
one() {  # name seconds trace program [prefix...]; uses and advances $next unless SEED is set
  local name=$1 s=$2 trace=$3 prog=$4; shift 4
  local seed=${SEED:-$next}; [ -z "${SEED:-}" ] && next=$((next + 1000))
  local t0=$(date +%s.%N)
  "$@" python3 "$prog" --workload "$cell" --seed "$seed" --seconds "$s" --trace "$trace" \
      > "$out/$name.out" 2> "$out/$name.err"
  local rc=$?
  last=$out/$name.err
  echo "{\"run\": \"$name\", \"seed\": $seed, \"rc\": $rc, \"wall_s\": $(python3 -c "import time,sys; print(round(time.time()-float(sys.argv[1]),1))" "$t0"), \"line\": $(tail -n 1 "$out/$name.out" | grep '^{' || echo null)}" | tee -a "$out/summary.jsonl"
}
run=benchmarks/run.py
for stage in "$@"; do
  n=${stage#*:}
  case $stage in
    first) one first "$secs" 0 $run ;;
    sets:*)
      a0=$next
      for k in $(seq 1 "$n"); do one "a$k" "$secs" 0 $run; done
      for k in $(seq 1 "$n"); do SEED=$((a0 + 1000 * (k - 1))) one "b$k" "$secs" 0 $run; done ;;
    short:*) for k in $(seq 1 "$n"); do one "short$k" 10 0 $run; done ;;
    runs:*:*)
      s=${stage##*:}; n=${stage#runs:}; n=${n%%:*}
      for k in $(seq 1 "$n"); do one "w$s-$k" "$s" 0 $run; done ;;
    control:*) for k in $(seq 1 "$n"); do one "control$k" 10 0 benchmarks/tools/control.py; done ;;
    trace:*) for k in $(seq 1 "$n"); do one "trace$k" "$secs" 1 $run; done ;;
    starved)
      third=$(( ($(nproc) + 2) / 3 ))
      one starved "$secs" 0 $run taskset -c 0-$((third - 1)) ;;
    coldvariant)
      cache=${JAX_COMPILATION_CACHE_DIR:-benchmarks/_work/xla_cache}
      key=$(grep -o '"jit__direct_cube", "[a-z]*", "[0-9a-f]\{12\}"' "$last" | head -n 1 | grep -o '[0-9a-f]\{12\}')
      echo "{\"cold_variant_removed\": \"$(ls "$cache" | grep "jit__direct_cube-$key" | tr '\n' ' ')\"}" | tee -a "$out/summary.jsonl"
      [ -n "$key" ] && rm -f "$cache"/jit__direct_cube-"$key"*
      one coldvariant "$secs" 0 $run ;;
  esac
done
du -sm benchmarks/_work | tee -a "$out/summary.jsonl"
