#!/bin/sh
# Runs of one cell in one call, for spreads and bounds:
#   sh bench/tools/sets.sh <cell> <seconds> <seed:trace> ...
# appends one line per run to $OUT/sets_<cell>.jsonl (default .bench_out):
# the seed, the exit code, the wall time, the run's information line and
# its result line; keeps each run's standard error as $OUT/err_<cell>_<n>.txt.
cell=$1; s=$2; shift 2
dir=${OUT:-.bench_out}
mkdir -p $dir
out=$dir/sets_$cell.jsonl
n=0
for a in "$@"; do
  n=$((n + 1)); seed=${a%%:*}; tr=${a##*:}
  t0=$(date +%s)
  python3 bench/run.py --workload $cell --seconds $s --seed $seed --trace $tr \
    > $dir/stdout.txt 2> $dir/err_${cell}_$n.txt
  rc=$?
  info=null; line=null
  if [ $rc -eq 0 ]; then
    info=$(tail -n 2 $dir/stdout.txt | head -n 1)
    line=$(tail -n 1 $dir/stdout.txt)
  fi
  echo "{\"seed\": $seed, \"trace\": $tr, \"rc\": $rc, \"wall_s\": $(( $(date +%s) - t0 )), \"info\": $info, \"result\": $line}" >> $out
  echo "seed $seed trace $tr rc $rc wall $(( $(date +%s) - t0 ))"; tail -n 2 $dir/err_${cell}_$n.txt
done
rm -f $dir/stdout.txt
