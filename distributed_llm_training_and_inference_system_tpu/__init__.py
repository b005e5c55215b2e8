"""TPU-native distributed LLM training and inference framework.

A ground-up rebuild of the capability surface of
``ambicuity/Distributed-LLM-Training-and-Inference-System`` (the ``llmctl``
CLI scaffold), architected for TPU: SPMD over ``jax.sharding.Mesh`` with
pjit/shard_map, XLA collectives over ICI, Pallas kernels for the hot ops,
and a single Python process per host instead of torchrun-per-rank.

Subpackages (each one implements FOR REAL a package that is empty or
stubbed in the reference — see SURVEY.md §2):

- ``config``    typed schemas + TOML/JSON IO      (reference llmctl/config: EMPTY)
- ``models``    decoder-only transformers in JAX  (reference: HF AutoModel passthrough)
- ``ops``       Pallas kernels + XLA fallbacks    (reference llmctl/exec: EMPTY)
- ``parallel``  mesh/sharding/planner/pipeline    (reference llmctl/partition: EMPTY)
- ``comms``     collective layer over mesh axes   (reference llmctl/comms: EMPTY)
- ``exec``      train step / optimizer / remat    (reference llmctl/exec: EMPTY)
- ``io``        data streaming + sharded ckpt     (reference llmctl/io: EMPTY)
- ``runtime``   engine + launchers                (reference llmctl/runtime)
- ``serve``     paged-KV continuous-batching srv  (reference llmctl/serve)
- ``metrics``   observability + health            (reference llmctl/metrics)
- ``plugins``   autotuning (real measurements)    (reference llmctl/plugins)
- ``cli``       the 13 llmctl commands, un-stubbed (reference llmctl/cli)

Import as::

    import distributed_llm_training_and_inference_system_tpu as dlts
"""

import time as _time

# where ``llmctl.startup.import`` begins (metrics/spans.py STARTUP): the
# package's first line, before anything heavy is imported. Nothing else may
# be imported here: config-only commands never pay for jax.
_IMPORT_T0 = _time.monotonic()

__version__ = "0.1.0"
