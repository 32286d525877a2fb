#!/bin/sh
# Limit readings of one cell, then runs of it, in one call:
#   sh bench/tools/limits.sh <cell> <seeds> <control seeds> <seconds> <seed:trace>...
# writes $OUT/limits_<cell>.jsonl and each run's standard error under $OUT
# (default .bench_out).
cell=$1; seeds=$2; cseeds=$3; s=$4; shift 4
out=${OUT:-.bench_out}
mkdir -p $out
python3 bench/limits.py --workload $cell --seeds $seeds --control-seeds "$cseeds" > $out/limits_$cell.jsonl 2> $out/limits_err.txt
echo "limits rc $?"; cat $out/limits_$cell.jsonl; tail -n 3 $out/limits_err.txt
for a in "$@"; do
  seed=${a%%:*}; tr=${a##*:}
  echo "=== seed $seed trace $tr"
  t0=$(date +%s)
  python3 bench/run.py --workload $cell --seconds $s --seed $seed --trace $tr 2> $out/err_$seed.txt | tail -c 4000
  echo "rc $? wall $(( $(date +%s) - t0 ))"
  tail -n 3 $out/err_$seed.txt
done
