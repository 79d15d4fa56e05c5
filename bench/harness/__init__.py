"""The benchmark's harness: drivers, traffic generation, trace reduction,
peaks, operation and byte counts, compile counting and the result line.

Everything that belongs to one configuration, traffic mix or per-layer
metric lives in a file of its own under ``bench/configs``, ``bench/traffic``
or ``bench/metrics``; :mod:`bench.harness.registry` finds it by the name
that ``BENCHMARK.json`` gives.
"""
