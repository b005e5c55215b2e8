"""The tokens a benchmark configuration serves, for comparing two trees.

    chiprun -- python experiments/served_tokens.py --config mistral-7b-16l \\
        --out chiprun_out/tokens.json

Builds the engine as the benchmark's serving runner does (seeded random
weights at the configuration's widths and ``serve`` block) and generates 8
prompts of ragged lengths x 64 tokens twice: greedy, and sampled at
temperature 0.8 with a seed a request. Two trees that do the same arithmetic
in the same order write the same file. Fails (exit 2) without a TPU.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import harness
from distributed_llm_training_and_inference_system_tpu.config import schema
from distributed_llm_training_and_inference_system_tpu.models import gpt
from distributed_llm_training_and_inference_system_tpu.serve.engine import (
    InferenceEngine)
from distributed_llm_training_and_inference_system_tpu.serve.scheduler import (
    Request, SamplingParams)

PROMPT_LENGTHS = (33, 64, 97, 190, 256, 411, 700, 1000)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="mistral-7b-16l")
    ap.add_argument("--seed", type=int, default=3000000319)
    ap.add_argument("--out", required=True)
    a = ap.parse_args()
    if jax.devices()[0].platform != "tpu":
        print("served_tokens: no TPU", file=sys.stderr)
        return 2
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           a.config + ".json")) as f:
        config = json.load(f)
    model_cfg = schema.ModelConfig.from_dict(harness.model_dict(config))
    serve_cfg = schema.ServeConfig(model=config["name"], **config["serve"])
    params = jax.jit(lambda key: gpt.init(
        model_cfg, key, jnp.dtype(serve_cfg.dtype)))(
            jax.random.PRNGKey(a.seed % (2 ** 31 - 1)))
    engine = InferenceEngine(model_cfg, serve_cfg, params=params)
    rng = np.random.default_rng(a.seed)
    prompts = [rng.integers(1, model_cfg.vocab_size, n).tolist()
               for n in PROMPT_LENGTHS]
    out = {}
    for mode, temperature in (("greedy", 0.0), ("seeded", 0.8)):
        reqs = [Request(request_id=f"{mode}-{i}", prompt_tokens=p,
                        sampling=SamplingParams(
                            temperature=temperature, max_tokens=64,
                            seed=None if mode == "greedy" else 1000 + i))
                for i, p in enumerate(prompts)]
        for r in reqs:
            if not engine.scheduler.add_request(r):
                raise RuntimeError(r.error)
        engine.run_until_idle()
        out[mode] = [r.generated_tokens for r in reqs]
    os.makedirs(os.path.dirname(a.out) or ".", exist_ok=True)
    with open(a.out, "w") as f:
        json.dump(out, f)
    print(json.dumps({mode: [len(t) for t in toks]
                      for mode, toks in out.items()}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
