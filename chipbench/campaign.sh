# Run one cell once per seed, each in its own process, at BENCHMARK.json's
# run_seconds; append each run's last line to chipbench_out/runs/<cell>.jsonl
# and print the lines of its standard error that say what it measured.
#
#   bash chipbench/campaign.sh <workload> <trace 0|1> <seed> [<seed> ...]
#
# The sets that the bounds are set from: two calls with the same six seeds.
w=$1; t=$2; shift 2
secs=$(python3 -c 'import json; print(json.load(open("BENCHMARK.json"))["run_seconds"])')
out=chipbench_out/runs
mkdir -p "$out"
for s in "$@"; do
  python3 chipbench/run.py --workload "$w" --seed "$s" --seconds "$secs" \
      --trace "$t" > "$out/stdout.txt" 2> "$out/stderr.txt"
  rc=$?
  line=$(tail -n 1 "$out/stdout.txt" | python3 -c 'import sys, json; s = sys.stdin.read().strip(); print(s if s.startswith("{") else json.dumps(s))')
  echo "{\"workload\": \"$w\", \"seed\": $s, \"trace\": $t, \"rc\": $rc, \"line\": $line}" >> "$out/$w.jsonl"
  grep -E "^window|^reference|^setup_s|^check|roofline|Error|error" "$out/stderr.txt" | tail -14 | sed "s/^/[$w $s] /"
done
