#!/usr/bin/env bash
# Runs of benchmark cells in ONE chip call, each run's whole output kept
# under chiprun_out/ and its result line printed (PERF.md quotes them).
#
#   scripts/bench_runs.sh TAG DIR TRACE CELL SEED [CELL SEED ...]
#
# DIR is a checkout to run from: `.` for the tree, or a directory that
# .gitignore lists holding another commit (`git archive <commit> | tar -x -C
# .bench_checkout/parent`), with this PR's benchmark files laid over it where
# the comparison asks for that. A parent-against-change reading is four of
# these in one `chiprun -- bash -c '...'`, in the order parent, change,
# change, parent, each pair of sides at the same seeds. Never stops at a
# failing run: the exit code of each is printed. BENCH_RUNNER names another
# script with bench/run.py's arguments (scripts/bench_engine_counters.py,
# which adds the engine's step-in-flight counters to the log). BENCH_SECONDS
# shortens the window where only `setup_s` is read (a cold run that fills
# the compile cache, the warm runs after it: PERF.md section 6, PR 63).
set -u
tag=$1 dir=$2 trace=$3
shift 3
root=$(cd "$(dirname "$0")/.." && pwd)
seconds=${BENCH_SECONDS:-$(python3 -c "import json; print(json.load(open('$root/BENCHMARK.json'))['run_seconds'])")}
mkdir -p "$root/chiprun_out"
while [ $# -ge 2 ]; do
    cell=$1 seed=$2
    shift 2
    log="$root/chiprun_out/${tag}_${cell}_${seed}_t${trace}.log"
    (cd "$root/$dir" && python3 "${BENCH_RUNNER:-bench/run.py}" --workload "$cell" --seed "$seed" \
        --seconds "$seconds" --trace "$trace") >"$log" 2>&1
    echo "$tag $cell $seed trace$trace exit $?"
    grep -a "reference check\|^engine counters" "$log" | cut -c1-400
    grep -a "^set-up \|^ *[0-9.]* s  " "$log" | cut -c1-300
    tail -n 1 "$log" | cut -c1-3000
done
