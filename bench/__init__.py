"""On-chip benchmark of the E+P+D serving engine (see PERF.md).

``bench/run.py`` is the one entry point.  Everything that belongs to one
model configuration, one traffic mix or one per-layer metric is a file of
its own, found by the name that ``BENCHMARK.json`` gives it:

  bench/configs/<config>.json     sizes as run, source, cuts, deployment,
                                  and its language model's model_type
  bench/arch/<model_type>.py      what the architecture asks of the
                                  harness: the program's ModelConfig, the
                                  weights' shapes, the bytes a token takes
                                  in the pools, FLOP and byte counts, and
                                  the plain f32 reference
  bench/traffic/<traffic>.json    lengths, arrivals, limits, sampling
  bench/metrics/<metric>.py       one reader per per-layer metric

The rest of this package is the yardstick that those files parameterise:
the traffic generator, the weights, the plain f32 reference, the FLOP and
byte counters with the table of peaks, and the trace reduction.
"""
