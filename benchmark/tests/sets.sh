#!/bin/bash
# Two sets of runs of one cell with the same seeds, then one traced run.
# usage: sets.sh <cell> <seconds> <runs-per-set> <out>   (from the checkout
# root; <out> is where logs and records go, e.g. /root/repo/chiprun_out)
cell=$1; secs=$2; n=$3; out=$4
seeds=(1000003 2147483659 77777777 2000000011 123456789 2147483999)
mkdir -p $out/sets $out/out
for set in A B; do
  for i in $(seq 0 $((n-1))); do
    s=${seeds[$i]}
    python3 benchmark/run.py --workload $cell --seed $s --seconds $secs --trace 0 > $out/sets/$cell.$set.$s.log 2> $out/sets/$cell.$set.$s.err
    rc=$?
    echo "$set $s rc=$rc $(tail -n 1 $out/sets/$cell.$set.$s.log | cut -c1-700)"
    # what else the run's records say of its gaps: spread.py tabulates it
    [ $rc -eq 0 ] && python3 benchmark/tests/gap_stats.py benchmark/out $cell | tee -a $out/sets/$cell.$set.$s.gaps
    grep -h "^reference\|^set-up\|^samples\|^paths" $out/sets/$cell.$set.$s.log | cut -c1-400
    if [ $rc -ne 0 ] && [ $set = A ] && [ $i -eq 0 ]; then
      tail -n 30 $out/sets/$cell.$set.$s.err; exit $rc  # broken: stop here
    fi
  done
done
python3 benchmark/run.py --workload $cell --seed 424242 --seconds $secs --trace 1 > $out/sets/$cell.T.log 2> $out/sets/$cell.T.err
rc=$?
echo "T rc=$rc $(tail -n 1 $out/sets/$cell.T.log | cut -c1-4000)"
grep -h "^reference\|^set-up\|^per chip\|^samples\|^paths" $out/sets/$cell.T.log | cut -c1-600
[ $rc -ne 0 ] && tail -n 30 $out/sets/$cell.T.err
# the records of a backlog run are tens of MB: the small ones come back
find benchmark/out -name "$cell.*.json" -size -4M -exec cp {} $out/out/ \;
