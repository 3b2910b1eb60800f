#!/bin/bash
# The knee sweep of gpt2m.chat (PR 23): run.py itself at fixed rates, 30 s
# windows, rate 4.0 three times with different seeds to see the spread.
#   chiprun --chips 1 --timeout 2400 -- bash benchmark/sweeps/sweep_chat.sh
cd "$(dirname "$0")/../.."
mkdir -p chiprun_out/sweep
seed=3100000000
for rate in 4.0 4.0 4.0 4.5 5.0 5.5; do
  seed=$((seed + 1))
  out=chiprun_out/sweep/chat_${rate}_${seed}.txt
  python3 benchmark/run.py --workload gpt2m.chat --seed $seed --seconds 30 --trace 0 \
      --set rate_per_s=$rate > $out 2>&1
  echo "rate $rate seed $seed rc=$?"
  grep -E "window open|requests:|in flight|lateness|latency_p95_ms:|reference" $out | cut -c1-400
  tail -1 $out | python3 -c "import sys,json; d=json.loads(sys.stdin.read()); print({k:v['value'] for k,v in d['metrics'].items()}, d['correct'], d['device']['memory_peak_bytes'])"
done
