"""The benchmark: data files, one command (`python bench/run.py`) and the
yardstick (traffic generation, reference, trace reduction, peaks, costs).
See bench/README.md."""
