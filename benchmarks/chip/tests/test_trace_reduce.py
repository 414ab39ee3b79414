"""The trace reduction on a synthetic XSpace with known times."""
import pytest

import trace_reduce

# Device ops (ns): kernel 0-1000, fusion 1500-2000, kernel 2000-3000,
# then a gap to 10000, fusion 10000-10500; a while op spans 0-3000.  Busy = 1000 + 500 + 1000 +
# 500 = 3000 ns.  Gaps: 1000-1500 (500 ns), 3000-10000 (7000 ns).  The
# module jit_tick spans 0-3000 and jit_other 10000-10500.  Host: a
# bench.labels span covers 2500-9000 (most of the long gap), a
# PjitFunction event covers 1000-1500.
XSPACE = """
planes {
  id: 1
  name: "/device:TPU:0"
  lines {
    id: 1
    name: "XLA Ops"
    timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 0 duration_ps: 1000000 }
    events { metadata_id: 2 offset_ps: 1500000 duration_ps: 500000 }
    events { metadata_id: 1 offset_ps: 2000000 duration_ps: 1000000 }
    events { metadata_id: 2 offset_ps: 10000000 duration_ps: 500000 }
    events { metadata_id: 5 offset_ps: 0 duration_ps: 3000000 }
  }
  lines {
    id: 2
    name: "XLA Modules"
    timestamp_ns: 0
    events { metadata_id: 3 offset_ps: 0 duration_ps: 3000000 }
    events { metadata_id: 4 offset_ps: 10000000 duration_ps: 500000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%_edge_spmm_blocked.3 = f32[8,128]{1,0} custom-call(s32[3]{0} %a), custom_call_target=\\"tpu_custom_call\\"" } }
  event_metadata { key: 2 value { id: 2 name: "%fusion.7 = f32[8,6]{1,0} fusion(f32[8,128]{1,0} %_edge_spmm_blocked.3)" } }
  event_metadata { key: 5 value { id: 5 name: "%while.9 = (s32[], f32[8,6]{1,0}) while((s32[], f32[8,6]{1,0}) %t)" } }
  event_metadata { key: 3 value { id: 3 name: "jit_tick(42)" } }
  event_metadata { key: 4 value { id: 4 name: "jit_other(7)" } }
}
planes {
  id: 2
  name: "/host:CPU"
  lines {
    id: 1
    name: "python"
    timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 2500000 duration_ps: 6500000 }
    events { metadata_id: 2 offset_ps: 1000000 duration_ps: 500000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench.labels" } }
  event_metadata { key: 2 value { id: 2 name: "PjitFunction(tick)" } }
}
"""


@pytest.fixture(scope="module")
def profile():
    from jax.profiler import ProfileData

    return ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(XSPACE))


def test_busy_kernels_modules(profile):
    s = trace_reduce.reduce_profile(
        profile, {"edge_spmm_nb": r"^%_edge_spmm_blocked[.\d]* = .*custom-call\("},
        {"tick": "jit_tick"})
    assert s["chips"] == 1
    assert s["busy_s"] == pytest.approx(3000e-9)
    assert s["kernels"]["edge_spmm_nb"]["count"] == 2
    assert s["kernels"]["edge_spmm_nb"]["total_s"] == pytest.approx(2000e-9)
    assert s["modules"]["tick"] == {"count": 1,
                                    "total_s": pytest.approx(3000e-9)}
    # the while op spans its body: busy once, and not a device op
    names = [n for n, _ in s["device_ops"]]
    assert names == ["%_edge_spmm_blocked.3 custom-call", "%fusion.7 fusion"]
    assert s["device_ops"][1][1] == pytest.approx(1000e-9)


def test_op_label():
    assert trace_reduce.op_label(
        "%while.96 = (s32[]{:T(128)}, f32[1,8,6]{2,1,0:T(8,128)}) "
        "while((s32[]{:T(128)}, f32[1,8,6]) %tuple.1)") == "%while.96 while"
    assert trace_reduce.op_label(
        "%_edge_spmm_blocked.16 = f32[524288,128]{1,0:T(8,128)} "
        "custom-call(s32[32769]{0:T(1024)S(1)} %reduce.9)") \
        == "%_edge_spmm_blocked.16 custom-call"
    assert trace_reduce.op_label("odd") == "odd"


def test_idle_gaps_named_by_host_activity(profile):
    s = trace_reduce.reduce_profile(profile, {}, {})
    (n1, g1), (n2, g2) = s["idle_gaps"]
    assert (n1, n2) == ("bench.labels", "PjitFunction(tick)")
    assert g1 == pytest.approx(7000e-9) and g2 == pytest.approx(500e-9)


def test_no_device_plane_raises():
    from jax.profiler import ProfileData

    host_only = XSPACE[XSPACE.index("planes {\n  id: 2"):]
    pd = ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(host_only))
    with pytest.raises(ValueError, match="no TPU device plane"):
        trace_reduce.reduce_profile(pd, {}, {})


def test_merge_intervals():
    assert trace_reduce.merge_intervals([(5, 7), (0, 2), (1, 3), (7, 8)]) \
        == [[0, 3], [5, 8]]


def _xspace(ops, modules):
    """A one-chip XSpace: ``ops`` and ``modules`` are (HLO text or module
    name, start ns, duration ns)."""
    names = sorted({n for n, _, _ in ops} | {n for n, _, _ in modules})
    ids = {n: i + 1 for i, n in enumerate(names)}
    ev = lambda rows: "".join(
        f"    events {{ metadata_id: {ids[n]} offset_ps: {s * 1000} "
        f"duration_ps: {d * 1000} }}\n" for n, s, d in rows)
    meta = "".join(
        f"  event_metadata {{ key: {i} value {{ id: {i} name: "
        f"\"{n.replace(chr(34), chr(92) + chr(34))}\" }} }}\n"
        for n, i in ids.items())
    return (f"planes {{\n  id: 1\n  name: \"/device:TPU:0\"\n"
            f"  lines {{\n    id: 1\n    name: \"XLA Ops\"\n"
            f"    timestamp_ns: 0\n{ev(ops)}  }}\n"
            f"  lines {{\n    id: 2\n    name: \"XLA Modules\"\n"
            f"    timestamp_ns: 0\n{ev(modules)}  }}\n{meta}}}\n")


def test_kernel_feed_is_the_ops_that_make_its_operands():
    from jax.profiler import ProfileData

    clamp = "%clamp_fusion.1 = s32[64]{0} fusion(s32[64]{0} %p.0)"
    gather = ("%fusion.2 = f32[64,6]{1,0} fusion(f32[16,6]{1,0} %bitcast.9, "
              "s32[64]{0} %clamp_fusion.1), kind=kCustom, calls=%fc.1")
    pad = ("%pad.3 = f32[64,128]{1,0} pad(f32[64,6]{1,0} %fusion.2, f32[] "
           "%constant.1..sunk), padding=0_0x0_122")
    other_kernel = ("%mu_eg_update.4 = f32[16,128]{1,0} custom-call("
                    "f32[16,128]{1,0} %x), custom_call_target=\"tpu\"")
    slice_ = ("%slice_fusion.5 = f32[16,6]{1,0} fusion(f32[16,128]{1,0} "
              "%mu_eg_update.4)")
    kernel = ("%_edge_spmm_blocked.6 = f32[16,128]{1,0} custom-call("
              "f32[64,128]{1,0} %pad.3, f32[16,6]{1,0} %slice_fusion.5), "
              "custom_call_target=\"tpu_custom_call\"")
    after = "%fusion.7 = f32[16,6]{1,0} fusion(f32[16,128]{1,0} %_edge_spmm_blocked.6)"
    ops = [(other_kernel, 0, 100), (slice_, 100, 10), (clamp, 110, 20),
           (gather, 130, 200), (pad, 330, 100), (kernel, 430, 500),
           (after, 930, 30),
           # the same program again, then a name of another module
           (clamp, 2110, 20), (gather, 2130, 200), (pad, 2330, 100),
           (kernel, 2430, 500), (pad, 5000, 999)]
    modules = [("jit_tick(1)", 0, 1000), ("jit_tick(1)", 2000, 1000),
               ("jit_other(2)", 5000, 999)]
    pd = ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(_xspace(ops, modules)))
    s = trace_reduce.reduce_profile(
        pd, {"edge_spmm_nb": r"^%_edge_spmm_blocked[.\d]* = .*custom-call\("},
        {"tick": "jit_tick"})
    k = s["kernels"]["edge_spmm_nb"]
    assert k["count"] == 2 and k["total_s"] == pytest.approx(1000e-9)
    # gather, pad and clamp twice, the slice of the other kernel's output
    # once; not the other kernel, nor the op after, nor jit_other's pad
    assert k["feed_s"] == pytest.approx(2 * 320e-9 + 10e-9)
    assert [n for n, _ in k["feed_ops"]] == [
        "%fusion.2 fusion", "%pad.3 pad", "%clamp_fusion.1 fusion",
        "%slice_fusion.5 fusion"]


def test_operands():
    assert trace_reduce.operands(
        "%pad.3 = f32[64,128]{1,0:T(8,128)} pad(f32[64,6]{1,0:T(8,128)} "
        "%fusion.2, f32[]{:T(128)} %constant.1..sunk), padding=0_0x0_122") \
        == {"%fusion.2", "%constant.1..sunk"}
