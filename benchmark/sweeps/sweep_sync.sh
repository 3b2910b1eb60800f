#!/bin/bash
# The knee sweep of the candidate landcover.sync (PR 23): run.py at fixed rates, 20 s windows.
#   chiprun --chips 1 --timeout 1500 -- bash benchmark/sweeps/sweep_sync.sh
cd "$(dirname "$0")/../.."
mkdir -p chiprun_out/sweep
seed=3300000000
for rate in ${RATES:-40 80 120 160 200 240}; do
  seed=$((seed + 1))
  out=chiprun_out/sweep/sync_${rate}_${seed}.txt
  python3 benchmark/run.py --manifest benchmark/candidates.json --workload landcover.sync --seed $seed --seconds 20 --trace 0 \
      --set rate_per_s=$rate > $out 2>&1
  echo "rate $rate seed $seed rc=$?"
  grep -E "requests:|in flight|lateness|latency_p95_ms:" $out | cut -c1-300
  tail -1 $out | python3 -c "import sys,json; d=json.loads(sys.stdin.read()); print({k:v['value'] for k,v in d['metrics'].items()}, d['correct'])"
done
