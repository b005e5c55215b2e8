"""Tokens the engine credited to requests in the window a second of its own
clock (``slot_steps.tokens_credited`` delta / ``clock_s`` delta): the
ledger's side of ``serve_tokens_per_s``, which the clients count. The two
differ by the window's edges (the engine's snapshots against the clients'
stamps) and by tokens credited and not yet streamed."""
import sys

from benchmark import facts, slot_step_counters


def read(run):
    w = slot_step_counters.window(run)
    if w is None or w["clock_s"] <= 0:
        return None
    w0, w1 = run["window"]
    classes = slot_step_counters.CLASSES
    print(f"[bench] engine.ledger_tokens_per_s: {w['tokens_credited']} tokens"
          f" credited ({w['first_tokens']} of them first tokens) in "
          f"{w['clock_s']:.3f} s; the clients counted "
          f"{facts.tokens_in_window(run)} in {w1 - w0:.3f} s = "
          f"{facts.tokens_in_window(run) / (w1 - w0):.3f} tokens/s; slot-steps "
          + ", ".join(f"{k} {w[k]}" for k in classes)
          + f" add to {sum(w[k] for k in classes)} of {w['decode_steps']} "
          f"steps x {w['slots']} slots = {w['decode_steps'] * w['slots']}",
          file=sys.stderr)
    return w["tokens_credited"] / w["clock_s"]
