"""Comms layer: collective semantics + measured (not simulated) benchmarks."""

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from distributed_llm_training_and_inference_system_tpu.comms import (
    all_gather, all_to_all, allreduce_sum, bench_all, reduce_scatter,
    ring_shift)


def _mesh(devices8):
    import numpy as np
    return Mesh(np.asarray(devices8).reshape(8), ("x",))


def test_collective_semantics(devices8):
    mesh = _mesh(devices8)
    x = jnp.arange(16, dtype=jnp.float32).reshape(8, 2)

    def body(v):
        return (allreduce_sum(v, "x"), all_gather(v, "x"),
                reduce_scatter(all_gather(v, "x"), "x"),
                ring_shift(v, "x"))

    fn = jax.jit(shard_map(body, mesh=mesh, in_specs=(P("x", None),),
                           out_specs=(P("x", None), P(None, None),
                                      P("x", None), P("x", None)),
                           check_vma=False))
    ar, ag, rs, perm = fn(x)
    np.testing.assert_allclose(np.asarray(ar)[0], x.sum(0))      # psum
    np.testing.assert_allclose(np.asarray(ag), x)                # gather = identity
    np.testing.assert_allclose(np.asarray(rs), 8 * x)            # rs(ag) = n*x... no:
    # reduce_scatter over the gathered copy sums 8 identical rows blocks


def test_ring_shift_rotates(devices8):
    mesh = _mesh(devices8)
    x = jnp.arange(8, dtype=jnp.float32).reshape(8, 1)
    fn = jax.jit(shard_map(lambda v: ring_shift(v, "x"), mesh=mesh,
                           in_specs=(P("x", None),), out_specs=P("x", None)))
    out = np.asarray(fn(x)).ravel()
    np.testing.assert_allclose(out, np.roll(np.arange(8), 1))


def test_all_to_all_transposes(devices8):
    mesh = _mesh(devices8)
    x = jnp.arange(64, dtype=jnp.float32).reshape(8, 8)
    fn = jax.jit(shard_map(
        lambda v: all_to_all(v, "x", split_dim=1, concat_dim=0),
        mesh=mesh, in_specs=(P("x", None),), out_specs=P(None, "x")))
    out = np.asarray(fn(x))
    np.testing.assert_allclose(out, x.T.reshape(8, 8).T)  # shape preserved
    assert out.shape == (8, 8)


def test_bench_measures_real_time(devices8):
    mesh = _mesh(devices8)
    results = bench_all(mesh, "x", size_mb=1.0)
    assert len(results) == 5
    for r in results:
        assert r["time_ms"] > 0.0
        assert np.isfinite(r["bus_bandwidth_gbps"])


# -- comms.hlo: reading a partitioned program's text ---------------------------

_HLO = '''HloModule jit_step, entry_computation_layout={()->f32[]}

%add.1 (x: f32[], y: f32[]) -> f32[] {
  %x = f32[] parameter(0)
  %y = f32[] parameter(1)
  ROOT %s = f32[] add(%x, %y)
}

%all-reduce-scatter.2.clone (input.1: bf16[2048,8192]) -> bf16[512,8192] {
  %input.1 = bf16[2048,8192]{1,0} parameter(0)
  %all-reduce.7 = bf16[2048,8192]{1,0:T(8,128)(2,1)} all-reduce(%input.1), channel_id=3, replica_groups={{0,1,2,3}}, to_apply=%add.1
  ROOT %dynamic-slice.1 = bf16[512,8192]{1,0} dynamic-slice(%all-reduce.7), dynamic_slice_sizes={512,8192}
}

%body.3 (p: (s32[], f32[512,92544])) -> (s32[], f32[512,92544]) {
  %p = (s32[], f32[512,92544]{1,0}) parameter(0)
  %all-gather.9 = bf16[2048,92544]{1,0:T(8,128)(2,1)} all-gather(%w), channel_id=1, replica_groups=[1,4]<=[4], dimensions={0}, metadata={op_name="jit(step)/while/body/jvp(chunked_loss)/while/body/bsh,hv->bsv/dot_general" stack_frame_id=7}
  %fusion.5 = bf16[512,8192]{1,0} fusion(%g), kind=kCustom, calls=%all-reduce-scatter.2.clone, metadata={op_name="jit(step)/while/body/jvp(chunked_loss)/while/body/transpose"}
  %all-reduce-start.1 = (f32[4,512]{1,0}, f32[4,512,1]{2,1,0}) all-reduce-start(%a, %b), channel_id=4, to_apply=%add.1, metadata={op_name="jit(step)/while/body/jvp(chunked_loss)/while/body/reduce_max"}
  %all-reduce-done.1 = (f32[4,512]{1,0}, f32[4,512,1]{2,1,0}) all-reduce-done(%all-reduce-start.1)
  ROOT %t = (s32[], f32[512,92544]{1,0}) tuple(%i, %acc)
}

%cond.3 (p.1: (s32[], f32[512,92544])) -> pred[] {
  %p.1 = (s32[], f32[512,92544]{1,0}) parameter(0)
  ROOT %lt = pred[] compare(%i.1, %n), direction=LT
}

ENTRY %main.1 () -> f32[] {
  %while.1 = (s32[], f32[512,92544]{1,0}) while(%init), condition=%cond.3, body=%body.3, metadata={op_name="jit(step)/while"}
  %all-gather.2 = f32[4,4096,2048]{2,1,0} all-gather(%e), channel_id=9, dimensions={0}, metadata={op_name="jit(step)/scatter-add"}
  ROOT %r = f32[] constant(0)
}
'''


def test_hlo_collectives_are_read_with_their_loops():
    from distributed_llm_training_and_inference_system_tpu.comms.hlo import (
        collectives)
    found = {c.name: c for c in collectives(_HLO)}
    assert set(found) == {"all-reduce.7", "all-gather.9",
                          "all-reduce-start.1", "all-gather.2"}
    head = found["all-gather.9"]
    assert (head.op, head.shapes) == ("all-gather",
                                      (("bf16", (2048, 92544)),))
    assert head.nbytes == 2048 * 92544 * 2 and head.has_axis(92544)
    assert head.in_loop and head.fusion == ""
    assert head.loop == "jit(step)/while/body/jvp(chunked_loss)/while"
    # an all-reduce whose fusion keeps one shard: named for what it is, under
    # the calling fusion's loop and name (what a device trace shows)
    scatter = found["all-reduce.7"]
    assert scatter.op == "all-reduce-scatter" and scatter.fusion == "fusion.5"
    assert scatter.in_loop and "chunked_loss" in scatter.loop
    assert not scatter.overlapped
    # an async pair is one transfer; a tuple result counts every member
    stats = found["all-reduce-start.1"]
    assert stats.op == "all-reduce" and len(stats.shapes) == 2
    assert stats.nbytes == 2 * 4 * 512 * 4
    assert stats.widest == ("f32", (4, 512)) and not stats.has_axis(92544)
    outside = found["all-gather.2"]
    assert not outside.in_loop and outside.loop == ""
