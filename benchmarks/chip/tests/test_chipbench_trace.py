"""The trace reduction on a small recorded trace: busy union, idle share,
kernel time and roofline share by op name and direction, self time of
nested ops, and idle gaps named by the host span open over them."""

import json

import pytest

import chipbench_cells  # noqa: F401  (the repository root on sys.path)

from benchmarks.chip import harness, tracing
from benchmarks.chip.metrics import _attention

MS = 1e6     # ns
CELL = harness.find_cell(harness.load_benchmark(), "train.opt-125m.s2048")

# one chip, a 100 ms window: a loop (10-60 ms) holding two kernels, a
# fusion right after it, and a lone op after an idle gap
FWD = "%flash_attention_bhsd.27 = (bf16[6,12,2048,64]{3,2,1,0}, f32[6]) custom-call(%a)"
BWD = "%flash_attention_bhsd.28 = f32[6,12,2048,64]{3,2,1,0} custom-call(%b)"
OPS = [
    ("%while.8 = (s32[], bf16[6,2048,768]) while(%t)", 10 * MS, 50 * MS),
    (FWD, 12 * MS, 10 * MS),
    (BWD, 30 * MS, 20 * MS),
    ("%fusion.3 = f32[6,2048,50304]{2,1,0} fusion(%c)", 60 * MS, 10 * MS),
    ("%copy.1 = f32[4]{0} copy(%d)", 90 * MS, 5 * MS),
]
HOST = [("bench.traced", 0.0, 100 * MS), ("bench.data", 70 * MS, 15 * MS),
        ("bench.step", 0.0, 9 * MS)]
# the timed step's HLO: the kernels' op_names give their direction
CORE = "while/body/closed_call/attention/attention_core/" \
    "jit(flash_attention_bhsd)/pallas_call"
HLO = f"""HloModule jit_train_step
ENTRY %main.1 (a: bf16[8]) -> bf16[8] {{
  %flash_attention_bhsd.27 = (bf16[6,12,2048,64]{{3,2,1,0}}, f32[6]) custom-call(%a), metadata={{op_name="jit(train_step)/jvp()/{CORE}"}}
  %flash_attention_bhsd.28 = f32[6,12,2048,64]{{3,2,1,0}} custom-call(%b), metadata={{op_name="jit(train_step)/transpose(jvp())/{CORE}"}}
}}
"""


def recorded():
    steps = [("jit_train_step(123)", 10 * MS, 60 * MS)]
    return tracing.Trace(devices={0: OPS}, modules={0: steps}, host=HOST)


def test_busy_is_the_union_of_nested_and_overlapping_ops():
    # 10..70 covered (loop, its kernels, the fusion), then 90..95
    assert tracing.busy_ns(OPS, 0, 100 * MS) == pytest.approx(65 * MS)
    assert tracing.busy_ns(OPS, 20 * MS, 60 * MS) == pytest.approx(40 * MS)


def test_idle_share_and_gaps_named_by_host_span():
    s = tracing.summarize(recorded(), "bench.traced")
    assert s["window_s"] == pytest.approx(0.1)
    assert s["busy_s"] == pytest.approx(0.065)
    gaps = dict((k, v) for k, v in s["breakdown"]["idle_gaps"])
    # 70..90 ms: the host was drawing data; 0..10 ms: dispatching
    assert gaps["bench.data"] == pytest.approx(0.020)
    assert gaps["bench.step"] == pytest.approx(0.010)
    assert gaps["-"] == pytest.approx(0.005)        # 95..100: no span open
    assert s["breakdown"]["idle_gaps"][0][0] == "bench.data"


def test_self_time_leaves_out_nested_ops():
    t = tracing.self_times(OPS)
    assert t["while.8 s32[]"] == pytest.approx(20 * MS)     # 50 - 10 - 20
    assert t["flash_attention_bhsd.27 bf16[6,12,2048,64]"] == \
        pytest.approx(10 * MS)


def obs_for(trace):
    """What the training runner hands the readers after a traced run."""
    return {"trace": trace,
            "trace_summary": tracing.summarize(trace, "bench.traced"),
            "config": CELL.config, "traffic": CELL.traffic,
            "family": CELL.family, "device_kind": "TPU v5 lite",
            "step_module": "jit_train_step", "step_hlo": lambda: HLO}


@pytest.mark.parametrize("bwd_writes", ["f32", "bf16"])
def test_kernel_time_by_name(bwd_writes):
    """The kernels by name, their direction by the op_name map, whatever
    dtype the backward writes; an op the map lacks counts for neither."""
    from benchmarks.chip import scopes
    fwd = harness.metric_reader("attn_fwd_roofline.train").PATTERN
    bwd = harness.metric_reader("attn_bwd_roofline.train").PATTERN
    ops = [(n.replace("= f32[", f"= {bwd_writes}["), a, d) for n, a, d in OPS]
    names = scopes.hlo_op_names(HLO)
    assert _attention.kernel_ns(ops, names, fwd, False, 0, 100 * MS) == \
        10 * MS
    assert _attention.kernel_ns(ops, names, bwd, True, 0, 100 * MS) == \
        20 * MS
    assert _attention.kernel_ns(ops, names, bwd, True, 35 * MS, 100 * MS) \
        == 15 * MS
    del names["flash_attention_bhsd.28"]
    assert _attention.kernel_ns(ops, names, bwd, True, 0, 100 * MS) == 0


def test_roofline_share_against_hand_count():
    obs = obs_for(recorded())
    # forward: 4 B H hd S(S+1)/2 = 4*6*12*64*2048*2049/2 per layer, 12
    # layers in the one step, against 10 ms of kernel time
    need = 12 * 4 * 6 * 12 * 64 * 2048 * 2049 / 2 / 197e12
    got = harness.metric_reader("attn_fwd_roofline.train").read(obs)
    assert got == pytest.approx(100 * need / 0.010)
    got = harness.metric_reader("attn_bwd_roofline.train").read(obs)
    assert got == pytest.approx(100 * 2 * need / 0.020)
    idle = harness.metric_reader("device_idle_share.train").read(obs)
    assert idle == pytest.approx(35.0)


def test_reader_without_its_kernel_reads_nothing():
    tr = recorded()
    tr.devices[0] = [op for op in OPS if "flash" not in op[0]]
    obs = obs_for(tr)
    assert harness.metric_reader("attn_fwd_roofline.train").read(obs) is None
    assert harness.metric_reader("attn_bwd_roofline.train").read(obs) is None
    # nor a traced run whose step's op_name map could not be had
    obs = dict(obs_for(recorded()), step_hlo=lambda: 1 / 0)
    assert harness.metric_reader("attn_fwd_roofline.train").read(obs) is None


def test_breakdown_is_json_with_at_most_ten_entries():
    many = [(f"%fusion.{i} = f32[8]{{0}} fusion()", i * MS, 0.5 * MS)
            for i in range(40)]
    tr = tracing.Trace(devices={0: many}, host=[("bench.traced", 0, 50 * MS)])
    b = tracing.summarize(tr, "bench.traced")["breakdown"]
    assert len(b["device_ops"]) == 10 and len(b["idle_gaps"]) == 10
    json.dumps(b)
