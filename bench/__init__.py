"""Chip benchmark of the served HTTP/SSE path.

`python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>`
runs one cell of `BENCHMARK.json` once and prints one JSON result line.
Everything that belongs to one configuration, traffic mix, metric or
model kind is a file of its own, found by name:

    bench/configs/<config>.json      sizes, source, engine size, knee rate
    bench/traffic/<traffic>.json     parameters of the one generator
    bench/metrics/<metric>.py        reader of one metric: read(run)
    bench/reference/<kind>.py        plain f32 forward of one model kind
"""
