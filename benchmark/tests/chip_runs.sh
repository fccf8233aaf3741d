#!/bin/bash
# The builder's tool for chip calls: runs of the benchmark's one command,
# one after another in ONE call, each with its own seed.
#   chiprun --chips 1 --timeout 3000 -- bash benchmark/tests/chip_runs.sh <tag> <workload>:<seed>:<seconds>:<trace>[:<control>[:<rate_rps>[:<more,seeds>[:<reference,controls>]]]] ...
# Every run's stdout, result, trace summary and log tails land in
# chiprun_out/runs/<tag>/<n>-<workload>-t<trace>[-<control>]/ and one
# summary line per run in chiprun_out/runs/<tag>/summary.jsonl.
set -u
cd "$(dirname "$0")/../.."
tag=$1; shift
top=chiprun_out/runs/$tag
mkdir -p "$top"
n=0
for spec in "$@"; do
  IFS=: read -r workload seed seconds trace control rate more refs <<< "$spec"
  n=$((n + 1))
  dir=$top/$n-$workload-t$trace${control:+-$control}${rate:+-r$rate}
  mkdir -p "$dir"
  t0=$(date +%s.%N)
  python3 benchmark/tests/builder.py --workload "$workload" --seed "$seed" --seconds "$seconds" \
      --trace "$trace" ${control:+--control "$control"} ${rate:+--rate-rps "$rate"} \
      ${more:+--more-seeds "$more"} ${refs:+--reference-controls "$refs"} > "$dir/stdout" 2> "$dir/stderr"
  rc=$?
  t1=$(date +%s.%N)
  src=chiprun_out/benchmark/$workload
  for f in result.json trace.json reference.json; do [ -f "$src/$f" ] && cp "$src/$f" "$dir/"; done
  [ -f "$src/supervisor.log" ] && tail -c 200000 "$src/supervisor.log" > "$dir/supervisor.log.tail"
  [ "${KEEP_EVENTS:-0}" = 1 ] && [ -f "$src/trace.json.events.json.gz" ] && cp "$src/trace.json.events.json.gz" "$dir/"
  python3 - "$dir" "$spec" "$rc" "$t0" "$t1" >> "$top/summary.jsonl" <<'PY'
import json, sys
d, spec, rc, t0, t1 = sys.argv[1:6]
lines = open(d + "/stdout").read().strip().splitlines()
last = lines[-1] if lines else ""
try:
    last = json.loads(last)
except ValueError:
    pass
controls = [json.loads(l) for l in lines if l.startswith(('{"phase": "control"', '{"phase": "more-seed"'))]
print(json.dumps({"spec": spec, "rc": int(rc), "wall_s": round(float(t1) - float(t0), 1),
                  "last": last, "controls": controls}))
PY
  tail -n 1 "$top/summary.jsonl" | cut -c1-1800
done
