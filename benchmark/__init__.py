"""The yardstick: everything BENCHMARK.json's command runs (see PERF.md).

Only ``drivers/`` names the program's symbols; the rest is the
benchmark's own and may not be edited by a PR that claims a gain.
"""
