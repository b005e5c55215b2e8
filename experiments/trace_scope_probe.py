"""What a device trace says of an XLA operation traced under a named scope
(PR 31): the event's name and every stat of a few operations of a small
jitted function, so that a reader of per-scope seconds
(benchmark/runners/hybrid.py ``scope_seconds``) knows where the scope's
name can be found. Run on the chip: ``chiprun -- python
experiments/trace_scope_probe.py``."""

import shutil
import sys
import tempfile

sys.path.insert(0, ".")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from benchmark import trace_reduce  # noqa: E402


def step(h, x):
    with jax.named_scope("ssm_decode"):
        h = h * jnp.exp(-x)[..., None] + x[..., None]
        y = jnp.sum(h * 0.5, axis=-1)
    with jax.named_scope("ssm_gated_norm"):
        y = y * jax.nn.silu(x)
    return h, y


def main() -> None:
    h = jnp.ones((64, 64, 64, 128), jnp.float32)
    x = jnp.ones((64, 64, 64), jnp.float32)
    fn = jax.jit(step, donate_argnums=0)
    h, y = fn(h, x)
    jax.block_until_ready(y)
    d = tempfile.mkdtemp(prefix="probe_trace_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(d, profiler_options=opts)
    for _ in range(3):
        h, y = fn(h, x)
    jax.block_until_ready(y)
    jax.profiler.stop_trace()
    profile = jax.profiler.ProfileData.from_file(trace_reduce.find_xplane(d))
    for plane in profile.planes:
        if not plane.name.startswith("/device:TPU:0"):
            continue
        for line in plane.lines:
            print("LINE", line.name)
            for ev in list(line.events)[:4]:
                print("  EVENT", ev.name[:300], ev.duration_ns)
                try:
                    for k, v in ev.stats:
                        print("     STAT", k, "=", str(v)[:300])
                except Exception as e:       # what the API does not give
                    print("     stats unreadable:", e)
    shutil.rmtree(d, ignore_errors=True)


if __name__ == "__main__":
    main()
