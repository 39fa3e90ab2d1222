"""The chip benchmark: one cell of BENCHMARK.json per run.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one configuration, one traffic mix or one
per-layer metric lives in a file of its own (`configs/`, `traffic/`,
`metrics/`, `refs/`); the modules here are the parts every cell shares.
"""
