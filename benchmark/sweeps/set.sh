#!/bin/bash
# One set of runs of one cell, each with another seed, in one call:
#   bash benchmark/sweeps/set.sh <workload> <seconds> <trace> <seed>...
cd "$(dirname "$0")/../.."
w=$1; secs=$2; trace=$3; shift 3
mkdir -p chiprun_out/sets
for seed in "$@"; do
  out=chiprun_out/sets/${w}_t${trace}_${seed}_$(date +%H%M%S).txt
  python3 benchmark/run.py --workload $w --seed $seed --seconds $secs --trace $trace > $out 2>&1
  echo "== $w seed $seed rc=$?"
  grep -E "requests:|in flight|lateness|compile phases" $out | cut -c1-300
  tail -1 $out | python3 -c "
import sys,json
d=json.loads(sys.stdin.read())
print(json.dumps({'m':{k:v['value'] for k,v in d['metrics'].items()},'ok':d['correct'],'n':d['attempted'],'f':d['failed'],'mem':d['device'].get('memory_peak_bytes'),'busy':d['device'].get('busy_s'),'win':d['device'].get('window_s'),'ref':d['reference'],'notes':d.get('notes')}))
print(json.dumps(d.get('breakdown',{}))[:3000])"
done
