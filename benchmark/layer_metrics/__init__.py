"""One reader per per-layer metric, found by the metric's name."""
from benchmark.readers import loader

load = loader(__path__[0])
