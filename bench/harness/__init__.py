"""The benchmark's general code: data, traffic, index set-up, the served
window, the plain reference and its comparison, and the trace reduction.

Everything that belongs to one configuration, one traffic mix or one
metric lives in its own file under `bench/configs`, `bench/workloads`,
`bench/metrics` or `bench/work`, found by the name `BENCHMARK.json` gives.
"""
