"""Device time of the decode step's paged-attention kernel in the traced
stretch / decode steps on the device (executions x steps per dispatch).
Reads ``run["trace"]["device_ops"]``, the ten operations that took most
time, by the name the program gives the kernel (``paged_attention``; the
multi-query kernel of a suffix prefill is ``paged_attention_mq`` and is not
counted). A program whose kernels carry no names has no such line, and
neither has a run in which the kernel is not among those ten: that is said
on stderr (``trace_reduce`` keeps no seconds by operation name beyond the
list; PERF.md section 7)."""
import sys


def read(run):
    trace = run["trace"]
    n, _ = trace.get("programs", {}).get("decode", (0, 0.0))
    ops = trace.get("device_ops", [])
    seconds = sum(s for name, s in ops if "paged_attention" in name
                  and "paged_attention_mq" not in name)
    if n and ops and not seconds:
        # the list holds the ten longest operations only: a kernel that
        # falls under the tenth is not read as 0, it is not read at all
        print("kernels.paged_attention_ms_per_decode_step: no "
              f"paged_attention operation among the "
              f"{len(ops)} listed (shortest {min(s for _, s in ops):.4f} s);"
              " metric left out", file=sys.stderr)
    if not n or not seconds:
        return None
    return 1e3 * seconds / (n * run["serve_cfg"]["decode_steps_per_dispatch"])
