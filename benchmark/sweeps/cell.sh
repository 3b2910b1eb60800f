#!/bin/bash
# The chip runs a new LM cell needs beside sweeps/set.sh, for any workload of
# the manifest (PR 32; run.py itself, nothing beside it):
#   chiprun --chips 1 --timeout 3000 -- bash benchmark/sweeps/cell.sh sweep <workload> <first seed> <rate>...
#       the knee sweep: 30 s windows, --trace 0, one run a rate, a seed each
#   chiprun --chips 1 --timeout 3400 -- bash benchmark/sweeps/cell.sh sets <workload> <sample> <seed>...
#       untraced 51 s runs that time the cell as it is committed and check only
#       <sample> of the configuration's sampled streams (a copy of the manifest
#       and of the configuration under chiprun_out/, reference.sample changed:
#       the check follows the window, so the latencies are the cell's own)
#   chiprun --chips 1 --timeout 1500 -- bash benchmark/sweeps/cell.sh trace <workload> <seed> [module:NAME]
#       one traced 51 s run that keeps its trace until the device seconds under
#       each jax.named_scope of module:NAME's list are printed
#   ... cell.sh pairs <workload> <parent dir> <seed>...
#       untraced 51 s runs, each seed on a parent checkout (a directory of
#       this tree) and on this tree: parent, change, change, parent, ...
#       (TRACE=1 in the environment: traced runs, every per-layer metric of
#       each side in the short line - how PR 42 held the folded names to the
#       parent's series)
cd "$(dirname "$0")/../.."
mode=$1; w=$2; shift 2
mkdir -p chiprun_out/cell
line() {  # the result line's numbers, short
  tail -1 "$1" | python3 -c "
import sys, json
d = json.loads(sys.stdin.read())
print(json.dumps({'m': {k: v['value'] for k, v in d['metrics'].items()}, 'ok': d['correct'], 'n': d['attempted'], 'f': d['failed'], 'mem': d['device'].get('memory_peak_bytes'), 'busy': d['device'].get('busy_s'), 'win': d['device'].get('window_s'), 'ref': d['reference'], 'notes': d.get('notes')}))"
  grep -E "requests:|in flight|lateness|compile phases" "$1" | cut -c1-300
}
case $mode in
sweep)
  seed=$1; shift
  for rate in "$@"; do
    seed=$((seed + 1))
    out=chiprun_out/cell/${w}_rate${rate}_${seed}.txt
    python3 benchmark/run.py --workload $w --seed $seed --seconds 30 --trace 0 --set rate_per_s=$rate > $out 2>&1
    echo "== $w rate $rate seed $seed rc=$?"; line $out
  done;;
sets)
  sample=$1; shift
  python3 - $w $sample <<'PY'
import json, os, sys
w, sample = sys.argv[1], int(sys.argv[2])
manifest = json.load(open("BENCHMARK.json"))
entry = next(x for x in manifest["workloads"] if x["name"] == w)
config = next(c for c in manifest["configs"] if c["name"] == entry["config"])
body = json.load(open(config["file"]))
body["reference"]["sample"] = sample
config["file"] = "chiprun_out/cell/config.json"
json.dump(body, open(config["file"], "w"))
json.dump(manifest, open("chiprun_out/cell/manifest.json", "w"))
PY
  for seed in "$@"; do
    out=chiprun_out/cell/${w}_t0_${seed}.txt
    python3 benchmark/run.py --manifest chiprun_out/cell/manifest.json --workload $w --seed $seed --seconds 51 --trace 0 > $out 2>&1
    echo "== $w seed $seed rc=$?"; line $out
  done;;
trace)
  seed=$1; scopes=${2:-}
  out=chiprun_out/cell/${w}_t1_${seed}.txt
  python3 benchmark/run.py --workload $w --seed $seed --seconds 51 --trace 1 --keep-trace > $out 2>&1
  echo "== $w traced seed $seed rc=$?"; line $out
  JAX_PLATFORMS=cpu python3 - "chiprun_out/benchmark/$w.$seed.t1" "$scopes" <<'PY'
import importlib, sys
sys.path.insert(0, ".")
from benchmark.lib import xplane_spans
work, scopes = sys.argv[1], sys.argv[2]
kwargs = {}
if scopes:
    module, name = scopes.split(":")
    kwargs["scopes"] = getattr(importlib.import_module(module), name)
summary = xplane_spans.summarize(xplane_spans.read_planes(work + "/trace"), **kwargs)
for name, program in summary["scopes"].items():
    print(name, "seconds", round(program["seconds"], 4), {k: round(v, 4) for k, v in sorted(program["by_scope"].items(), key=lambda kv: -kv[1]) if v > 0})
print("gap_attributed_share", summary.get("gap_attributed_share"), "idle_s", summary.get("idle_s"))
PY
  rm -rf chiprun_out/benchmark/$w.$seed.t1/trace;;
pairs)
  parent=$1; shift
  here=$PWD; order="parent change"
  for seed in "$@"; do
    for side in $order; do
      [ $side = parent ] && dir=$parent || dir=$here
      out=$here/chiprun_out/cell/${w}_${side}_t${TRACE:-0}_${seed}.txt
      (cd $dir && python3 benchmark/run.py --workload $w --seed $seed --seconds 51 --trace ${TRACE:-0} > $out 2>&1)
      echo "== [$side] $w seed $seed trace ${TRACE:-0} rc=$?"; line $out
    done
    [ "$order" = "parent change" ] && order="change parent" || order="parent change"
  done;;
*) echo "unknown mode $mode"; exit 2;;
esac
