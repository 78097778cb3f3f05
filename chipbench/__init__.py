"""chipbench — the repo's benchmark: one command (run.py), data files for
configurations, cells and per-layer metrics, and the yardstick code
(traffic, trace reduction, peaks, FLOP counts, plain references) that
later PRs may add to but not edit. See README.md."""
