"""Cost the int4 dequant-in-kernel Pallas matmul against the alternatives
(round-4; verdict r3 weak #5 said this had never been costed).

Per decode-shape matmul, scanned ITERS times inside one jit (per-dispatch
overhead dwarfs ms-scale kernels — same discipline as `llmctl tune sp`),
fenced by a scalar fetch:

  bf16        x @ W                      (2*in*out bytes/step)
  int8-xla    x @ dequant8(W)            (1*in*out, XLA fuses the dequant)
  int4-xla    x @ dequant4(W)            (the round-3 serving path: unpack
                                          chain defeats fusion)
  int4-pallas matmul_w4 in-kernel dequant (0.5*in*out streamed)

Usage: python experiments/int4_kernel_bench.py [B] [iters]
Prints one JSON line per (shape, variant).
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> None:
    B = int(sys.argv[1]) if len(sys.argv) > 1 else 8
    iters = int(sys.argv[2]) if len(sys.argv) > 2 else 50

    import jax
    import jax.numpy as jnp

    from distributed_llm_training_and_inference_system_tpu.ops.int4_matmul_pallas import (
        matmul_w4)
    from distributed_llm_training_and_inference_system_tpu.ops.int8_matmul_pallas import (
        matmul_w8)
    from distributed_llm_training_and_inference_system_tpu.ops.quantization import (
        dequantize_int4_groupwise, dequantize_int8,
        quantize_int4_groupwise, quantize_int8)

    interpret = jax.default_backend() != "tpu"
    shapes = [("gpt-1b.ffn", 2048, 5632), ("gpt-1b.attn", 2048, 2048),
              ("gpt-7b.ffn", 4096, 11008), ("gpt-7b.attn", 4096, 4096),
              # down-proj: the wide-REDUCTION case (in=11008) that
              # forced the whole-K W8 kernel to a 128-wide tile — the
              # round-5 k-split path exists for exactly this shape
              ("gpt-7b.ffn_dn", 11008, 4096)]

    # decode streams weights from HBM every step; a naive scan over ONE
    # weight tensor lets XLA park it in VMEM (measured "13 TB/s" bf16 —
    # impossible) and measure pure MXU time. Rotating across enough
    # copies that the set exceeds VMEM forces the streaming regime the
    # cost model cares about. The Pallas kernel needs no forcing (its
    # BlockSpecs DMA operands from HBM per call — measured exactly
    # packed-bytes/time without it).
    VMEM_BYTES = 128 * 1024 * 1024

    def rotated(arrs):
        per = sum(a.size * a.dtype.itemsize for a in arrs)
        n = max(2, VMEM_BYTES // per + 2)
        return [jnp.stack([a] * n) for a in arrs], n

    for name, n_in, n_out in shapes:
        w = jax.random.normal(jax.random.PRNGKey(0), (n_in, n_out),
                              jnp.float32) * 0.05
        x = jax.random.normal(jax.random.PRNGKey(1), (B, n_in),
                              jnp.bfloat16)
        wb = w.astype(jnp.bfloat16)
        q8, s8 = quantize_int8(w)
        p4, s4, c4 = quantize_int4_groupwise(w, group=128)
        (wb_r,), n_wb = rotated([wb])
        (q8_r, s8_r), n_q8 = rotated([q8, s8])
        (p4_r, s4_r), n_p4 = rotated([p4, s4])

        def scan_time(fn, ws, n_copies):
            """Per-iteration ms, two-window differenced (N vs 2N iters)
            so the per-dispatch constant (round trip + host overhead)
            cancels. The scan rotates through n_copies weight replicas
            (xs = copy index) so XLA cannot park the weights in VMEM, and
            the output feeds back with a tiny real coefficient so
            iterations serialise and nothing dead-code-eliminates.

            ``ws`` (the weight arrays) are EXPLICIT jit arguments — as
            closure captures they were serialised into the remote-compile
            payload, which the 7B shapes overflowed (HTTP 413; the r4
            battery-13 run silently lost every shape after gpt-1b.ffn
            to the same limit)."""
            def make(n):
                idx = jnp.arange(n, dtype=jnp.int32) % n_copies

                @jax.jit
                def run(x0, *ws):
                    def body(carry, i):
                        y = fn(carry, i, *ws)
                        return carry + y[:, :1].astype(carry.dtype) * 1e-12, None
                    out, _ = jax.lax.scan(body, x0, idx)
                    return out[0, 0]
                return run

            run1, run2 = make(iters), make(2 * iters)
            float(run1(x, *ws)); float(run2(x, *ws))      # compile + warm

            def best(run, reps=5):
                # min over repetitions: the per-dispatch
                # constant VARIES (single-sample differencing measured
                # negative times); the minimum of each window is the
                # quiet-host value, and differencing the minima cancels
                # the constant that remains
                b = 1e9
                for _ in range(reps):
                    t0 = time.perf_counter()
                    float(run(x, *ws))
                    b = min(b, time.perf_counter() - t0)
                return b
            return (best(run2) - best(run1)) / iters * 1e3

        variants = {
            "bf16": (lambda xx, i, w: xx @ w[i], (wb_r,), n_wb),
            "int8-xla": (lambda xx, i, q, sc: xx @ dequantize_int8(
                q[i], sc[i]), (q8_r, s8_r), n_q8),
            "int4-xla": (lambda xx, i, pk, sc: xx @ dequantize_int4_groupwise(
                pk[i], sc[i], c4, group=128), (p4_r, s4_r), n_p4),
            # the Pallas kernel's BlockSpecs stream from HBM per call —
            # no rotation needed (or possible without scalar-prefetch
            # plumbing); i is unused
            "int4-pallas": (lambda xx, i, pk, sc, ch: matmul_w4(
                xx, pk, sc, ch, group=128,
                interpret=interpret), (p4, s4, c4), 1),
            # round-5: W8A16 in-kernel dequant — must BEAT int8-xla
            # (whose dequant fuses) before serve routing defaults on
            "int8-pallas": (lambda xx, i, q, sc: matmul_w8(
                xx, q, sc, interpret=interpret), (q8, s8), 1),
        }
        bytes_per = {"bf16": 2 * n_in * n_out, "int8-xla": n_in * n_out,
                     "int4-xla": n_in * n_out // 2,
                     "int4-pallas": n_in * n_out // 2,
                     "int8-pallas": n_in * n_out}
        for vname, (fn, ws, n_copies) in variants.items():
            ms = scan_time(fn, ws, n_copies)
            bw = bytes_per[vname] / (ms / 1e3) / 1e9
            print(json.dumps({"shape": name, "in": n_in, "out": n_out,
                              "B": B, "variant": vname,
                              "ms": round(ms, 4),
                              "stream_gbps": round(bw, 1)}), flush=True)


if __name__ == "__main__":
    main()
