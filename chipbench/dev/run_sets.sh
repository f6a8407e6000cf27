# usage: bash chipbench/dev/run_sets.sh <cell> <seconds> <seed>...   (two sets, same seeds)
cell=$1; secs=$2; shift 2
mkdir -p chiprun_out
out=chiprun_out/sets_$cell.jsonl; log=chiprun_out/sets_$cell.log
: > $out; : > $log
for set in 1 2; do
  for seed in "$@"; do
    python chipbench/run.py --workload $cell --seed $seed --seconds $secs --trace 0 > chiprun_out/_run.txt 2>&1
    rc=$?
    grep "^# \(train\|check\|info\)" chiprun_out/_run.txt >> $log
    line=$(tail -n 1 chiprun_out/_run.txt)
    case "$line" in
      "{"*) echo "{\"set\": $set, \"seed\": $seed, \"rc\": $rc, \"line\": $line}" >> $out ;;
      *) echo "RUN FAILED set=$set seed=$seed rc=$rc"; tail -n 15 chiprun_out/_run.txt ;;
    esac
  done
done
python chipbench/dev/spread.py $out
