#!/usr/bin/env bash
# Bytes of persistent compile cache that ONE run of each cell leaves, from an
# empty cache of its own, and the run's wall seconds when cold (PERF.md
# section 7 quotes them). One chip call:
#
#   chiprun --timeout 1500 -- scripts/cache_footprint.sh [DIR]
#
# DIR is the checkout to run from (default `.`).
set -u
root=$(cd "$(dirname "$0")/.." && pwd)
dir=${1:-.}
mkdir -p "$root/chiprun_out"
for cell in $(python3 -c "import json; print(' '.join(w['name'] for w in json.load(open('$root/BENCHMARK.json'))['workloads']))"); do
    cache=$(mktemp -d)
    s=$(date +%s)
    (cd "$root/$dir" && JAX_COMPILATION_CACHE_DIR=$cache JAX_COMPILATION_CACHE_MAX_SIZE=-1 \
        python3 bench/run.py --workload "$cell" --seed 2147484401 --seconds 5 --trace 1) \
        >"$root/chiprun_out/footprint_${cell}.log" 2>&1
    echo "$cell exit $? wall $(( $(date +%s) - s )) s, cache $(du -sb "$cache" | cut -f1) bytes in $(ls "$cache" | wc -l) files"
    grep -a "^set-up" "$root/chiprun_out/footprint_${cell}.log" | cut -c1-200
    rm -rf "$cache"
done
