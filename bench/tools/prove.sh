#!/bin/bash
# The measurements a new cell needs, in one call:
#   bash bench/tools/prove.sh <cell> <first seed> <run_seconds> <out dir>
# two sets of six runs on the same six seeds, three traced runs on three more,
# and the sound program and its bfloat16 control on three more seeds, in one
# process, at the cell's own load and window.
W=$1; B=$2; RS=$3; O=$4
mkdir -p $O
for set in 1 2; do
  for i in 0 1 2 3 4 5; do
    s=$((B + i))
    python3 bench/run.py --workload $W --seed $s --seconds $RS --trace 0 > $O/set${set}_$s.out 2> $O/set${set}_$s.err
    echo "set $set seed $s rc=$?"
  done
done
for i in 6 7 8; do
  s=$((B + i))
  python3 bench/run.py --workload $W --seed $s --seconds $RS --trace 1 > $O/trace_$s.out 2> $O/trace_$s.err
  echo "trace seed $s rc=$?"
done
python3 bench/tools/calibrate.py readings --workload $W --seconds $RS --seeds $((B + 9)),$((B + 10)),$((B + 11)) --control > $O/control.out 2> $O/control.err
echo "control rc=$?"
python3 bench/tools/spread.py $O/set1_*.out -- $O/set2_*.out
python3 bench/tools/summ.py $O/trace_*.out $O/control.out
for f in $O/set1_$B.err $O/trace_$((B + 6)).err; do grep "window:\|ramp:\|counters\|paged_attn:\|reference check" $f | cut -c1-400; done
for f in $O/trace_*.out; do tail -1 $f | python3 -c "import json,sys; d=json.loads(sys.stdin.read()); print(json.dumps(d['breakdown'])[:1500]); print(d['device'])"; done
