"""The reduction from a profiler trace to device time, kernel calls and
idle gaps, on a small synthetic trace in the profiler's own format."""
from __future__ import annotations

import pytest

from bench import spec, trace_reduce as tr

# As on a TPU, an op on a device's "XLA Ops" line is named by its HLO text.
# Two devices.  On TPU:0 a custom call of the tiny attention payload's types
# runs 0-2 us and 1-3 us (overlapping), a fusion 5-6 us; on TPU:1 one fusion
# 0-1 us.  One host thread runs an event across TPU:0's idle 3-5 us.
ATT = ("%_lambda_.1 = bf16[1,4,128,128]{3,2,1,0:T(8,128)(2,1)} custom-call(bf16[1,4,128,128]{3,2,1,0} %a_0_.1, "
       "bf16[1,2,128,128]{3,2,1,0} %a_1_.1, bf16[1,2,128,128]{3,2,1,0} %a_2_.1), "
       "custom_call_target=\\\"tpu_custom_call\\\", operand_layout_constraints={bf16[1,4,128,128]{3,2,1,0}}")
TEXT = f'''
planes {{
  id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0
    events {{ metadata_id: 1 offset_ps: 0 duration_ps: 2000000 }}
    events {{ metadata_id: 1 offset_ps: 1000000 duration_ps: 2000000 }}
    events {{ metadata_id: 2 offset_ps: 5000000 duration_ps: 1000000 }}
  }}
  lines {{ id: 2 name: "XLA Modules" timestamp_ns: 0
    events {{ metadata_id: 3 offset_ps: 0 duration_ps: 6000000 }} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "{ATT}" }} }}
  event_metadata {{ key: 2 value {{ id: 2 name: "%fusion.2 = f32[8]{{0:T(128)}} fusion(f32[8]{{0}} %p), kind=kLoop" }} }}
  event_metadata {{ key: 3 value {{ id: 3 name: "jit__lambda(123)" }} }}
}}
planes {{
  id: 2 name: "/device:TPU:1"
  lines {{ id: 1 name: "XLA Ops" timestamp_ns: 0
    events {{ metadata_id: 1 offset_ps: 0 duration_ps: 1000000 }} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "fusion.7" }} }}
}}
planes {{
  id: 3 name: "/host:CPU"
  lines {{ id: 9 name: "caas-jet2_0" timestamp_ns: 0
    events {{ metadata_id: 1 offset_ps: 3000000 duration_ps: 1500000 }}
    events {{ metadata_id: 2 offset_ps: 0 duration_ps: 900000000 }} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "PjitFunction(make)" }} }}
  event_metadata {{ key: 2 value {{ id: 2 name: "thread-long" }} }}
}}
'''
TINY_ATT = {"B": 1, "H": 4, "KV": 2, "L": 128, "hd": 128, "causal": True, "window": None}


@pytest.fixture(scope="module")
def trace():
    from jax.profiler import ProfileData

    return tr.from_profile(ProfileData.from_text_proto(TEXT))


def test_planes_and_lines(trace):
    assert sorted(trace.ops) == [0, 1]
    assert [tr.label(e) for e in trace.ops[0]] == [
        "_lambda_.1 bf16[1,4,128,128] custom-call", "_lambda_.1 bf16[1,4,128,128] custom-call", "fusion.2 f32[8] fusion"]
    assert {e.name for e in trace.host} == {"PjitFunction(make)", "thread-long"}


def test_busy_is_the_union(trace):
    merged = tr.union(trace.ops[0])
    assert merged == [(0.0, 3000.0), (5000.0, 6000.0)]
    assert tr.busy_ns(merged) == 4000.0
    assert tr.busy_ns(tr.clip(merged, 2000.0, 5500.0)) == 1500.0
    assert tr.gaps(merged, 0.0, 8000.0) == [(3000.0, 5000.0), (6000.0, 8000.0)]


def test_kernel_calls_match_by_types(trace):
    types = {"att": spec.plugin("work", "flash_attention").hlo_types(TINY_ATT, "bfloat16"),
             "att32": spec.plugin("work", "flash_attention").hlo_types(TINY_ATT, "float32")}
    assert tr.kernel_calls(trace.ops[0], types) == {"att": (2, 4000.0)}
    assert tr.kernel_calls(trace.ops[1], types) == {}


def scan_call(x: str) -> tr.Event:
    return tr.Event(
        f"%_lambda_.1 = (f32[1,32,128]{{2,1,0}}, f32[1,16,128]{{2,1,0}}) custom-call({x}[1,32,128]{{2,1,0}} %a_0_.1, "
        "f32[1,32,128]{2,1,0} %a_1_.1, f32[1,32,16] %copy, f32[1,32,16] %copy.1, f32[16,128] %bitcast.4, "
        'f32[1,16,128] %bitcast.5), custom_call_target="tpu_custom_call", operand_layout_constraints='
        "{f32[1,32,128]{2,1,0}, f32[1,32,128]{2,1,0}}", 0.0, 10.0)


def test_payloads_of_one_kernel_are_told_apart_by_dtype():
    work = spec.plugin("work", "selective_scan")
    shape = {"B": 1, "chunk": 32, "di": 128, "N": 16}
    types = {"f32": work.hlo_types(shape, "float32"), "bf16": work.hlo_types(shape, "bfloat16")}
    # a float32 x names f32[1,32,128] three times in the call; the repeats in
    # the attributes do not count
    assert not tr.matches(scan_call("bf16"), types["f32"])
    assert tr.kernel_calls([scan_call("bf16")], types) == {"bf16": (1, 10.0)}
    assert tr.kernel_calls([scan_call("f32")], types) == {"f32": (1, 10.0)}


def test_top_ops_and_gap_labels(trace):
    top = tr.top_ops(trace.ops[0])
    assert top[0] == ("_lambda_.1 bf16[1,4,128,128] custom-call", 4e-6)
    assert top[1] == ("fusion.2 f32[8] fusion", 1e-6)
    assert tr.label(scan_call("f32")) == "_lambda_.1 (f32[1,32,128], ...) custom-call"
    labels = tr.gap_labels(tr.gaps(tr.union(trace.ops[0]), 0.0, 6000.0), trace.host)
    assert labels == [("idle during PjitFunction(make)", 2e-6)]
