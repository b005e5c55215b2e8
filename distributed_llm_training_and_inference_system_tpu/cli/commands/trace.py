"""`llmctl trace` — profiler trace capture and what the host did in each gap.

``capture`` runs real train steps, or with ``--serve`` a local engine under
synthetic requests, under the JAX profiler. ``summarize`` reads the
``.xplane.pb`` it leaves: device busy and idle share, device seconds by
program, and device 0's idle gaps added up by the ``llmctl.*`` host span
(``metrics/spans.py``, ``parallel/api.py``, ``io/data.py``) that covers each
gap. The reduction works on plain ``(name, start_s, end_s)`` tuples;
``load_profile`` is the only part that needs a profile.
"""

from __future__ import annotations

import re
import time
from collections import defaultdict
from pathlib import Path

import click

SPAN_PREFIX = "llmctl."
NO_SPAN = "no span"
INSIDE_PROGRAM = "(inside a program)"


@click.group(name="trace", invoke_without_command=True)
@click.pass_context
def app(ctx):
    """Profiler traces."""
    if ctx.invoked_subcommand is None:
        click.echo(ctx.get_help())


@app.command()
@click.option("--config", "config_file", default=None,
              type=click.Path(exists=True, dir_okay=False))
@click.option("--model", "model_name", default=None,
              help="Model template (when no --config).")
@click.option("--steps", default=5, show_default=True)
@click.option("--out", "out_dir", default="traces", show_default=True)
@click.option("--serve", "serve", is_flag=True, default=False,
              help="Trace a local serving engine under synthetic requests "
                   "instead of training steps.")
@click.option("--seconds", default=5.0, show_default=True,
              help="With --serve: new rounds of requests start for this "
                   "long, after the warm-up round.")
def capture(config_file, model_name, steps, out_dir, serve, seconds):
    """Capture a profiler trace of real training steps, or of serving."""
    if serve:
        if not model_name:
            raise click.UsageError("--serve needs --model")
        done = capture_serve(model_name, out_dir, seconds)
        click.echo(f"captured {done['requests']} requests "
                   f"({done['decode_steps']} decode steps) into {out_dir}")
        # the engine's own account of the same stretch, to hold against
        # summarize's "idle by span" (the device trace's gaps by the span
        # that covers them): busy and nothing in flight, by the span open
        click.echo(f"the engine was starved {done['starved_s']:.4f} s of "
                   f"{done['clock_s']:.4f}: " + ", ".join(
                       f"{name} {s:.4f}" for name, s in sorted(
                           done["starved_by_phase"].items(),
                           key=lambda kv: -kv[1])))
        click.echo(f"read it with: llmctl trace summarize {out_dir}")
        return
    from ...config.loader import load_run_config
    from ...config.presets import get_model_config
    from ...metrics.observability import engine_observer
    from ...runtime.engine import TrainingEngine

    overrides = {"training": {"max_steps": steps, "profile": True,
                              "profile_dir": out_dir,
                              "log_interval": max(steps // 2, 1)},
                 "checkpoint": {"interval_steps": 10_000_000}}
    cfg = load_run_config(config_file, cli_overrides=overrides)
    if model_name:
        cfg.model = get_model_config(model_name)
    engine = TrainingEngine(cfg, observer=engine_observer())
    final = engine.train(resume=False)
    click.echo(f"captured {steps} steps (final loss "
               f"{final.get('loss', float('nan')):.4f}) into {out_dir}")
    click.echo(f"read it with: llmctl trace summarize {out_dir}  (or "
               f"tensorboard --logdir {out_dir})")


def capture_serve(model_name: str, out_dir: str, seconds: float,
                  seed: int = 0) -> dict:
    """Rounds of 16 greedy requests over 8 slots (prompts of 64-256 random
    tokens, 16-61 output tokens, so that slots free and refill between
    dispatches; shorter where the model's context is), each run to the end
    by ``InferenceEngine.run_until_idle()``: one round to compile every
    program those sizes reach, then rounds under the profiler until
    ``seconds`` have passed."""
    import jax
    import numpy as np

    from ...config.presets import get_model_config
    from ...config.schema import ServeConfig
    from ...serve.engine import InferenceEngine
    from ...serve.scheduler import Request, SamplingParams

    model_cfg = get_model_config(model_name)
    max_seq_len = min(1024, model_cfg.max_position_embeddings)
    longest = min(256, max_seq_len - 64)    # a tiny test model's context
    dtype = "bfloat16" if jax.default_backend() == "tpu" else "float32"
    engine = InferenceEngine(model_cfg, ServeConfig(
        model=model_name, max_batch_size=8, dtype=dtype,
        max_seq_len=max_seq_len), seed=seed)
    rng = np.random.default_rng(seed)

    def one_round(tag) -> None:
        for i in range(16):
            n = min(64 * (1 + i % 4), longest)
            if not engine.scheduler.add_request(Request(
                    request_id=f"trace-{tag}-{i}",
                    prompt_tokens=rng.integers(
                        1, model_cfg.vocab_size, n).tolist(),
                    sampling=SamplingParams(temperature=0.0,
                                            max_tokens=16 + 3 * i))):
                raise click.ClickException("the engine refused a request")
        engine.run_until_idle()

    one_round("warm")
    before = engine.stats()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0        # it slows the host it measures
    jax.profiler.start_trace(out_dir, profiler_options=opts)
    rounds = 0
    try:
        end = time.monotonic() + seconds
        while time.monotonic() < end:
            one_round(rounds)
            rounds += 1
    finally:
        jax.profiler.stop_trace()
    after = engine.stats()
    return {"requests": 16 * rounds,
            "decode_steps": after["decode_steps"] - before["decode_steps"],
            "clock_s": after["clock_s"] - before["clock_s"],
            "starved_s": after["starved_s"] - before["starved_s"],
            "starved_by_phase": {
                name: s - before["starved_by_phase"].get(name, 0.0)
                for name, s in after["starved_by_phase"].items()}}


# -- from a profile to what the host did in each gap ---------------------------

def find_xplane(trace_dir: str) -> Path | None:
    found = sorted(Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    return found[-1] if found else None


def load_profile(path: str) -> dict:
    """{"devices": {plane: {"programs": [(name, s, e)], "ops": [...]}},
    "host_spans": {thread: [(name, s, e)]}} in seconds, from an
    ``.xplane.pb``. Devices are the ``/device:`` planes (lines ``XLA
    Modules`` and ``XLA Ops``); host spans are the ``llmctl.*`` events of
    every other plane, by the line (the thread) they were opened on.
    "page_walk" sums the ``live_pages`` / ``table_pages`` ids that every
    decode dispatch's span carries (serve/engine.py ``_submit_group``), and
    for a model with state-space layers its ``ssm_slot_steps`` (``K``
    layers: ``kda_slot_steps``), for one with window layers the rows its
    two kinds of layer saw (``window_rows``, ``window_rows_unwindowed``,
    ``full_rows``, ``ring_wraps``), the prompt
    rows that rode the dispatch's steps (``ride_rows``), for one
    with latent attention the bytes of a latent page over its layers
    (``latent_page_bytes``, the last seen, not a sum);
    "prefill_rows" the ``bucket`` (rows the program computed), ``tokens``
    less ``cached`` (the live ones), ``cached`` (the prompt tokens the
    prefix cache supplied) and ``state_carry`` (the tokens of chunk programs
    that read and wrote a slot's recurrent state) of every prefill span; "startup_programs" the
    ``(name, seconds)`` of every ``llmctl.startup.program`` span (a
    program's first call: metrics/spans.py ``StartupRecorder``)."""
    from jax.profiler import ProfileData
    profile = ProfileData.from_file(str(path))
    devices: dict = {}
    host_spans: dict = {}
    page_walk = {"live_pages": 0, "table_pages": 0, "ssm_slot_steps": 0,
                 "kda_slot_steps": 0, "latent_page_bytes": 0, "ride_rows": 0,
                 # window layers (serve/engine.py ``_window_ids``)
                 "window_rows": 0, "window_rows_unwindowed": 0,
                 "full_rows": 0, "ring_wraps": 0}
    prefill_rows = {"rows": 0, "tokens": 0, "cached": 0, "state_carry": 0}
    startup_programs: list = []
    for plane in profile.planes:
        is_device = plane.name.startswith("/device:")
        for line in plane.lines:
            key = {"XLA Modules": "programs", "XLA Ops": "ops"}.get(line.name)
            if is_device and key:
                devices.setdefault(plane.name, {"programs": [], "ops": []})[
                    key] = [(e.name, e.start_ns * 1e-9,
                             (e.start_ns + e.duration_ns) * 1e-9)
                            for e in line.events]
            elif not is_device:
                found = []
                for e in line.events:
                    if not e.name.startswith(SPAN_PREFIX):
                        continue
                    found.append((e.name, e.start_ns * 1e-9,
                                  (e.start_ns + e.duration_ns) * 1e-9))
                    if e.name == SPAN_PREFIX + "engine.decode.submit":
                        for key, value in e.stats:
                            if key == "latent_page_bytes":
                                page_walk[key] = int(value)
                            elif key in page_walk:
                                page_walk[key] += int(value)
                    elif e.name == SPAN_PREFIX + "startup.program":
                        startup_programs.append(
                            (str(dict(e.stats).get("name", "?")),
                             e.duration_ns * 1e-9))
                    elif e.name == SPAN_PREFIX + "engine.prefill.host":
                        ids = dict(e.stats)
                        # (a chunked prefill's first span carries its
                        # cached tokens and no bucket)
                        prefill_rows["cached"] += int(ids.get("cached", 0))
                        prefill_rows["state_carry"] += int(
                            ids.get("state_carry", 0))
                        if "bucket" in ids:
                            prefill_rows["rows"] += int(ids["bucket"])
                            prefill_rows["tokens"] += (
                                int(ids["tokens"]) - int(ids.get("cached", 0)))
                if found:
                    host_spans.setdefault(
                        f"{plane.name} | {line.name}", []).extend(found)
    return {"devices": devices, "host_spans": host_spans,
            "page_walk": page_walk, "prefill_rows": prefill_rows,
            "startup_programs": startup_programs}


def _union(intervals) -> list:
    out: list = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def self_segments(spans) -> list:
    """[(name, s, e)] in which each instant belongs to the INNERMOST span
    open at it (a span's self time), from the nested ``(name, s, e)`` spans
    of one thread, or from ``{thread: spans}`` thread by thread."""
    if isinstance(spans, dict):
        return [seg for one in spans.values() for seg in self_segments(one)]
    out, stack = [], []                      # stack of [name, end, cursor]
    for name, s, e in sorted(spans, key=lambda t: (t[1], -t[2])):
        while stack and stack[-1][1] <= s:
            top = stack.pop()
            if top[1] > top[2]:
                out.append((top[0], top[2], top[1]))
            if stack:
                stack[-1][2] = max(stack[-1][2], top[1])
        if stack and s > stack[-1][2]:
            out.append((stack[-1][0], stack[-1][2], s))
        stack.append([name, e, s])
    while stack:
        top = stack.pop()
        if top[1] > top[2]:
            out.append((top[0], top[2], top[1]))
        if stack:
            stack[-1][2] = max(stack[-1][2], top[1])
    return out


def attribute_gaps(programs: list, host_spans) -> dict:
    """{span name: idle seconds}: each gap between program executions on a
    device goes, whole, to the host span whose self time covers most of it
    (``no span`` where none touches it)."""
    busy = _union((s, e) for _, s, e in programs)
    segments = sorted(self_segments(host_spans), key=lambda t: t[1])
    total: dict = defaultdict(float)
    j = 0
    for (_, gap_s), (gap_e, _) in zip(busy, busy[1:]):
        while j < len(segments) and segments[j][2] <= gap_s:
            j += 1
        cover: dict = defaultdict(float)
        k = j
        while k < len(segments) and segments[k][1] < gap_e:
            name, s, e = segments[k]
            cover[name] += min(e, gap_e) - max(s, gap_s)
            k += 1
        owner = max(cover, key=cover.get) if cover else NO_SPAN
        total[owner] += gap_e - gap_s
    return dict(total)


def summarize_events(programs: list, ops: list, host_spans) -> dict:
    """Device 0's account of a trace from plain tuples: the window (first
    to last device event), busy seconds (union of operations), seconds and
    executions by program, and the idle seconds by host span; idle time
    between the operations of one execution is ``(inside a program)``."""
    events = ops or programs
    if not events:
        return {}
    t0 = min(s for _, s, _ in events)
    t1 = max(e for _, _, e in events)
    busy = sum(e - s for s, e in _union((s, e) for _, s, e in events))
    by_program: dict = defaultdict(lambda: [0, 0.0])
    for name, s, e in programs:
        cell = by_program[re.sub(r"\(.*$", "", name).strip()]
        cell[0] += 1
        cell[1] += e - s
    gaps = attribute_gaps(programs, host_spans)
    inside = (t1 - t0) - busy - sum(gaps.values())
    if inside > 0:
        gaps[INSIDE_PROGRAM] = inside
    idle = (t1 - t0) - busy
    named = sum(v for k, v in gaps.items() if k.startswith(SPAN_PREFIX))
    return {"window_s": t1 - t0, "busy_s": busy, "idle_s": idle,
            "programs": {k: tuple(v) for k, v in by_program.items()},
            "idle_by_span": gaps,
            "idle_named_share": named / idle if idle > 0 else 1.0}


def host_span_totals(host_spans: dict) -> dict:
    """{span name: (calls, self seconds)} over the whole trace."""
    total: dict = defaultdict(lambda: [0, 0.0])
    for one in host_spans.values():
        for name, _, _ in one:
            total[name][0] += 1
    for name, s, e in self_segments(host_spans):
        total[name][1] += e - s
    return {k: tuple(v) for k, v in total.items()}


@app.command()
@click.argument("trace_dir", type=click.Path(exists=True, file_okay=False))
def summarize(trace_dir):
    """Device busy and idle, seconds by program, idle gaps by host span."""
    path = find_xplane(trace_dir)
    if path is None:
        raise click.ClickException(f"no .xplane.pb under {trace_dir}")
    loaded = load_profile(path)
    click.echo(f"{path}")
    spans = loaded["host_spans"]
    if not loaded["devices"]:
        click.echo("no device plane in this trace (a CPU run): host spans "
                   "only")
    plane = min(loaded["devices"], default=None)      # device 0
    dev = loaded["devices"].get(plane, {"programs": [], "ops": []})
    acc = summarize_events(dev["programs"], dev["ops"], spans)
    if acc:
        w = acc["window_s"]
        click.echo(f"{plane}: window {w:.3f} s, busy {acc['busy_s']:.3f} s "
                   f"({100 * acc['busy_s'] / w:.1f} %), idle "
                   f"{acc['idle_s']:.3f} s ({100 * acc['idle_s'] / w:.1f} %)")
        click.echo("  device seconds by program:")
        for name, (n, sec) in sorted(acc["programs"].items(),
                                     key=lambda kv: -kv[1][1]):
            click.echo(f"    {name:<40} {n:>6} x {sec:9.4f} s "
                       f"({100 * sec / w:5.1f} %)")
        click.echo("  idle seconds by the host span covering each gap:")
        for name, sec in sorted(acc["idle_by_span"].items(),
                                key=lambda kv: -kv[1]):
            click.echo(f"    {name:<40} {sec:9.4f} s "
                       f"({100 * sec / max(acc['idle_s'], 1e-12):5.1f} % of "
                       f"idle)")
        click.echo(f"  {100 * acc['idle_named_share']:.1f} % of the idle "
                   f"seconds lie under a named {SPAN_PREFIX}* span")
    walk = loaded["page_walk"]
    if walk["table_pages"]:
        click.echo(f"paged attention walks {walk['live_pages']} live pages "
                   f"of {walk['table_pages']} in the block tables "
                   f"({100 * walk['live_pages'] / walk['table_pages']:.1f} "
                   f"%), summed over the decode dispatches")
    if walk["ssm_slot_steps"]:
        click.echo(f"state-space layers advanced {walk['ssm_slot_steps']} "
                   f"slot states (live slots x decode steps), summed over "
                   f"the decode dispatches")
    if walk["kda_slot_steps"]:
        click.echo(f"delta-rule (K) layers advanced {walk['kda_slot_steps']} "
                   f"slot states (live slots x decode steps), summed over "
                   f"the decode dispatches")
    if walk["window_rows_unwindowed"]:
        click.echo(f"window layers saw {walk['window_rows']} K/V rows of the "
                   f"{walk['window_rows_unwindowed']} they would have seen "
                   f"as full layers "
                   f"({100 * walk['window_rows'] / walk['window_rows_unwindowed']:.1f}"
                   f" %); full layers {walk['full_rows']}; "
                   f"{walk['ring_wraps']} ring wraps, summed over the decode "
                   f"dispatches")
    if loaded["prefill_rows"]["state_carry"]:
        click.echo(f"{loaded['prefill_rows']['state_carry']} prompt tokens "
                   f"went chunk by chunk through programs that read and "
                   f"wrote their slot's recurrent state")
    if walk["ride_rows"]:
        click.echo(f"{walk['ride_rows']} prompt rows rode the decode "
                   f"dispatches (prefilled as rows of the decode steps, no "
                   f"prefill program between two dispatches)")
    rows = loaded["prefill_rows"]
    if walk["latent_page_bytes"]:
        prompt = rows["tokens"] + rows["cached"]
        click.echo(f"latent attention walked {walk['live_pages']} pages of "
                   f"{walk['latent_page_bytes']} bytes; "
                   f"{100 * rows['cached'] / max(prompt, 1):.1f} % of prompt "
                   f"tokens came from the prefix cache")
    if rows["rows"]:
        click.echo(f"prefill computed {rows['rows']} rows for "
                   f"{rows['tokens']} tokens, "
                   f"{100 * rows['tokens'] / rows['rows']:.1f} %")
    totals = host_span_totals(spans)
    if spans:
        click.echo("host spans (calls, self seconds):")
        for name, (n, sec) in sorted(totals.items(), key=lambda kv: -kv[1][1]):
            click.echo(f"    {name:<40} {n:>6} x {sec:9.4f} s")
    startup = {k: v for k, v in totals.items()
               if k.startswith(SPAN_PREFIX + "startup.")}
    if startup:
        click.echo(f"start-up: {sum(sec for _, sec in startup.values()):.4f} "
                   f"s under llmctl.startup.* spans in this trace; programs "
                   f"first called (trace, lowering, compile or cache read):")
        for name, sec in sorted(loaded["startup_programs"],
                                key=lambda kv: -kv[1]):
            click.echo(f"    {name:<40} {sec:9.4f} s")
