"""The chip benchmark of geomx_tpu: harness, data files, trace reducer and
plain references.  `BENCHMARK.json` at the root of the repo names the
cells; everything that belongs to one configuration, one traffic mix, one
family or one per-layer metric is a file of its own under this directory,
found by name.  See PERF.md."""
