#!/usr/bin/env bash
# The two-directory rehearsal of a cell's set-up, as the driver sees it:
# two separate copies of the checkout, each with its own HOME, TMPDIR
# and compile cache, one discarded first run (which compiles) in each,
# then RUNS short runs alternating between them, every run a new
# process and another seed.  Prints one line a run: the set-up's phases,
# the cache's hits and misses, the names of the programs that missed.
#
#   chiprun -- bash benchmark/rehearse_setup.sh mistral7b-train 8 5
#
# With a fifth argument "sets" the two directories get the SAME seeds,
# RUNS each (a, b, a, b, ... on seed+2, seed+2, seed+3, ...): the two
# sets that a bound is set from, at the run length of BENCHMARK.json:
#
#   chiprun --timeout 3000 -- bash benchmark/rehearse_setup.sh \
#       mistral7b-chat 6 30 2147484000 sets
#
# Run it from the root of the checkout, on the machine with the chip.
set -euo pipefail
cell=${1:?usage: rehearse_setup.sh <workload> [runs] [seconds] [first_seed] [sets]}
runs=${2:-8}
seconds=${3:-5}
seed=${4:-2147484000}
mode=${5:-alternate}
base=.scratch/rehearse
errs=$PWD/chiprun_out/rehearse_err
out=chiprun_out/rehearse_${cell}.jsonl
rm -rf "$base"; mkdir -p "$base" chiprun_out "$errs"; : > "$out"
for side in a b; do
  mkdir -p "$base/$side" "$base/home_$side" "$base/tmp_$side"
  # what git would commit: everything but what .gitignore lists
  tar -c --exclude=./.git --exclude=./.scratch --exclude=./chiprun_out \
      --exclude=./.jax_cache --exclude='__pycache__' --exclude='*.so' \
      --exclude=./.pytest_cache . | tar -x -C "$base/$side"
done
one() {  # side seed tag
  ( cd "$base/$1" && env -u JAX_COMPILATION_CACHE_DIR -u BENCH_RUN \
      HOME="$PWD/../home_$1" XDG_CACHE_HOME="$PWD/../home_$1/.cache" \
      TMPDIR="$PWD/../tmp_$1" \
      python3 benchmark/run.py --workload "$cell" --seed "$2" \
      --seconds "$seconds" --trace 0 2>"$errs/${cell}_$1_$2.log" ) \
  | python3 -c '
import json, sys
side, tag = sys.argv[1], sys.argv[2]
rows = [json.loads(l) for l in sys.stdin if l.startswith("{")]
setup = next((r for r in rows if r.get("event") == "setup"), None)
last = rows[-1] if rows else {}
if setup is None or "correct" not in last:
    print(json.dumps({"side": side, "run": tag, "error": "no result"}))
    sys.exit(0)
win = next((r for r in rows if r.get("event") == "window"), {})
c = setup["compile"]
print(json.dumps({"side": side, "run": tag, "seed": setup["seed"],
    "setup_s": round(setup["setup_s"], 3),
    "phases": {k: round(v, 3) for k, v in setup["phases"].items()},
    "trace_s": round(c["trace_s"], 3), "lower_s": round(c["lower_s"], 3),
    "compile_or_load_s": round(c["backend_compile_s"], 3),
    "hits": c["cache_hits"], "misses": c["cache_misses"],
    "missed": c["missed"],
    "compiles_in_window": win.get("compiles_in_window"),
    "correct": last["correct"], "failed": last["failed"],
    "notes": {k: v for k, v in (win.get("notes") or {}).items()
              if k != "kv"},
    "checks": {k: v["value"] for k, v in last.get("checks", {}).items()},
    "metrics": {k: v["value"] for k, v in last["metrics"].items()}}))
' "$1" "$3" | tee -a "$out"
}
one a "$seed" first
one b "$((seed + 1))" first
if [ "$mode" = sets ]; then
  for i in $(seq 1 "$runs"); do
    one a "$((seed + 1 + i))" "$i"
    one b "$((seed + 1 + i))" "$i"
  done
else
  for i in $(seq 1 "$runs"); do
    if [ $((i % 2)) -eq 1 ]; then side=a; else side=b; fi
    one "$side" "$((seed + 1 + i))" "$i"
  done
fi
rm -rf "$base"
