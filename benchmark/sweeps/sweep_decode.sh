#!/bin/bash
# The knee sweep of olmoe.decode (PR 26): run.py itself at fixed rates, 30 s
# windows, 20 s ramp at the swept rate, --trace 0, one run a rate.
#   chiprun --chips 1 --timeout 3000 -- bash benchmark/sweeps/sweep_decode.sh [rate...]
cd "$(dirname "$0")/../.."
mkdir -p chiprun_out/sweep
seed=3260000000
rates=${@:-"2.5 3.0 3.5 4.0 4.5"}
for rate in $rates; do
  seed=$((seed + 1))
  out=chiprun_out/sweep/decode_${rate}_${seed}.txt
  python3 benchmark/run.py --workload olmoe.decode --seed $seed --seconds 30 --trace 0 \
      --set rate_per_s=$rate > $out 2>&1
  echo "rate $rate seed $seed rc=$?"
  grep -E "window open|requests:|in flight|lateness|latency_p95_ms:|reference" $out | cut -c1-400
  tail -1 $out | python3 -c "import sys,json; d=json.loads(sys.stdin.read()); print({k:v['value'] for k,v in d['metrics'].items()}, d['correct'], d['device']['memory_peak_bytes'])"
done
