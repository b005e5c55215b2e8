"""Device time of the decode step's grouped expert matmuls in the traced
stretch / decode steps on the device (executions x steps per dispatch).
Reads ``run["trace"]["device_ops"]``, the ten operations that took most
time, by the name the program gives the kernel: ``moe_gmm.<n>`` (three a
layer: gate, up, down). A prefill's kernels are ``moe_gmm_prefill.<n>`` and
are not counted. A program without the kernel has no such line, and neither
has a run in which it is not among those ten: that is said on stderr
(``trace_reduce`` keeps no seconds by operation name beyond the list)."""
import re
import sys


def seconds(run):
    ops = run["trace"].get("device_ops", [])
    return sum(s for name, s in ops if re.match(r"moe_gmm\.\d+", name)
               or name.startswith("moe_gmm:"))


def read(run):
    trace = run["trace"]
    n, _ = trace.get("programs", {}).get("decode", (0, 0.0))
    s = seconds(run)
    if n and trace.get("device_ops") and not s:
        print("kernels.moe_gmm_ms_per_decode_step: no moe_gmm operation "
              f"among the {len(trace['device_ops'])} listed; metric left "
              "out", file=sys.stderr)
    if not n or not s:
        return None
    return 1e3 * s / (n * run["serve_cfg"]["decode_steps_per_dispatch"])
