"""One reader per end-to-end metric, found by the metric's name."""
from benchmark.readers import loader

load = loader(__path__[0])
