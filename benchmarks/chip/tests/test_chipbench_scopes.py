"""The scope readers on a small recorded trace: device self time split by
the program's named scopes through the op_name map of the step the
runner timed, the new per-layer shares by hand count, and the compile
counter's reader."""

import types

import pytest

import chipbench_cells  # noqa: F401  (the repository root on sys.path)

from benchmarks.chip import flops, harness, layer_costs, peaks, scopes, \
    tracing

CELL = harness.find_cell(harness.load_benchmark(), "train.opt-125m.s2048")
SCOPES = CELL.family.SCOPES

MS = 1e6     # ns
BODY = "jit(train_step)/jvp()/while/body/closed_call"
BACK = "jit(train_step)/transpose(jvp())/while/body/closed_call"

# the compiled step's text, as ``as_text()`` prints it: a fusion whose
# op_name is only on its fused root, kernels, products, a loop
HLO = f'''HloModule jit_train_step, entry_computation_layout={{()->()}}

%fused_computation.1 (param_0.1: bf16[8]) -> bf16[8] {{
  %param_0.1 = bf16[8]{{0}} parameter(0)
  ROOT %tanh.1 = bf16[8]{{0}} tanh(bf16[8]{{0}} %param_0.1), metadata={{op_type="tanh" op_name="{BODY}/mlp/tanh"}}
}}

ENTRY %main.9 (p.1: bf16[8]) -> bf16[8] {{
  %p.1 = bf16[8]{{0}} parameter(0)
  %while.7 = (s32[], bf16[8]{{0}}) while(%p.1), condition=%cond.1, body=%body.1, metadata={{op_name="jit(train_step)/jvp()/while"}}
  %fusion.1 = bf16[8]{{0}} fusion(%p.1), kind=kLoop, calls=%fused_computation.1
  %dot.9 = bf16[8]{{0}} dot(%p.1, %p.1), metadata={{op_type="dot_general" op_name="{BODY}/attention/bsd,dhk->bshk/dot_general"}}
  %flash_attention_bhsd.27 = (bf16[6,12,2048,64]{{3,2,1,0}}, f32[6]) custom-call(%p.1), custom_call_target="tpu_custom_call", metadata={{op_name="{BODY}/attention/attention_core/jit(flash_attention_bhsd)/pallas_call"}}
  %flash_attention_bhsd.28 = f32[6,12,2048,64]{{3,2,1,0}} custom-call(%p.1), custom_call_target="tpu_custom_call", metadata={{op_name="{BACK}/attention/attention_core/jit(flash_attention_bhsd)/pallas_call"}}
  %dot.2 = bf16[8]{{0}} dot(%p.1, %p.1), metadata={{op_name="{BACK}/mlp/dot_general"}}
  %dot.3 = f32[8]{{0}} dot(%p.1, %p.1), metadata={{op_name="jit(train_step)/jvp(lm_head)/bsd,vd->bsv/dot_general"}}
  %fusion.4 = f32[8]{{0}} fusion(%p.1), kind=kLoop, calls=%fused_computation.1, metadata={{op_name="jit(train_step)/transpose(jvp(lm_head))/bsd,vd->bsv/dot_general"}}
  %fusion.8 = f32[8]{{0}} fusion(%p.1), kind=kLoop, calls=%fused_computation.1, metadata={{op_name="jit(train_step)/transpose(jvp(embed))/jit(_take)/scatter-add"}}
  %fusion.5 = f32[8]{{0}} fusion(%p.1), kind=kLoop, calls=%fused_computation.1, metadata={{op_name="jit(train_step)/optimizer/add"}}
  ROOT %copy.6 = bf16[8]{{0}} copy(%p.1)
}}
'''


def _ev(instr, start_ms, dur_ms):
    return (f"%{instr} = bf16[8]{{0}} op(%p)", start_ms * MS, dur_ms * MS)


def step_events(at):
    """One step of 76 ms starting at ``at`` ms: a 60 ms loop holding the
    layers' ops (16 ms of its own), then the head, embedding gradient,
    optimizer and a copy."""
    return [_ev("while.7", at, 60), _ev("fusion.1", at + 1, 5),
            _ev("dot.9", at + 7, 3),
            _ev("flash_attention_bhsd.27", at + 11, 10),
            _ev("flash_attention_bhsd.28", at + 22, 20),
            _ev("dot.2", at + 43, 6), _ev("dot.3", at + 60, 4),
            _ev("fusion.4", at + 64, 8), _ev("fusion.8", at + 72, 1),
            _ev("fusion.5", at + 73, 2), _ev("copy.6", at + 75, 1)]


def recorded():
    """Two whole steps inside the traced slice (0..200 ms) and one cut by
    its end, which does not count."""
    ops = step_events(10) + step_events(90) + step_events(170)
    steps = [("jit_train_step(1)", a * MS, 76 * MS) for a in (10, 90, 170)]
    return tracing.Trace(devices={0: ops}, modules={0: steps},
                         host=[("bench.traced", 0.0, 200 * MS)])


def obs_for(trace, hlo=HLO):
    """What the training runner hands the readers after a traced run,
    its step's HLO text being ``hlo`` (no compile)."""
    return {"config": CELL.config, "traffic": CELL.traffic,
            "family": CELL.family, "device_kind": "TPU v5 lite", "chips": 1,
            "trace": trace,
            "trace_summary": tracing.summarize(trace, "bench.traced"),
            "step_module": "jit_train_step", "step_hlo": lambda: hlo}


@pytest.fixture
def mapped():
    """The readers map the trace through ``HLO``."""
    return obs_for(recorded())


@pytest.mark.parametrize("op_name,want", [
    (f"{BODY}/mlp/dot_general", ("mlp", False)),
    (f"{BACK}/attention/attention_core/exp", ("attention_core", True)),
    ("jit(train_step)/transpose(jvp(attention_core))/x", ("attention_core",
                                                          True)),
    ("jit(train_step)/jvp(lm_head)/reduce_max", ("lm_head", False)),
    ("jit(train_step)/optimizer/add", ("optimizer", False)),
    ("jit(train_step)/jvp()/while/body/dynamic_update_slice", (None, False)),
    ("jit(train_step)/jvp(jit(_var))/mlp_like/square", (None, False)),
    ("", (None, False)),
])
def test_scope_of_takes_the_innermost_named_component(op_name, want):
    assert scopes.scope_of(op_name, SCOPES) == want


def test_fusion_takes_its_roots_op_name_and_every_instruction_maps():
    m = scopes.hlo_op_names(HLO)
    assert m["fusion.1"] == f"{BODY}/mlp/tanh"           # from its root
    assert m["fusion.4"].endswith("transpose(jvp(lm_head))/bsd,vd->bsv"
                                  "/dot_general")         # its own first
    assert m["copy.6"] == "" and "p.1" in m


def test_split_by_hand_and_scoped_plus_unscoped_is_the_step(mapped):
    s = scopes.read(mapped)
    assert s["steps"] == 2                                 # the cut step not
    want = {"mlp.fwd": 5, "mlp.bwd": 6, "attention.fwd": 3,
            "attention_core.fwd": 10, "attention_core.bwd": 20,
            "lm_head.fwd": 4, "lm_head.bwd": 8, "embed.bwd": 1,
            "optimizer.fwd": 2,
            "unscoped": 16 + 1}                             # loop's own + copy
    for k, ms in want.items():
        assert s[k] == pytest.approx(ms / 1e3), k
    assert s["total"] == pytest.approx(0.076)
    scoped = sum(scopes.scope_s(s, sc) for sc in SCOPES)
    assert scoped + s["unscoped"] == pytest.approx(s["total"], rel=1e-12)
    assert s["unmapped"] == 0.0
    assert "scoped + unscoped 76.000 of the step's 76.000" in \
        scopes.table(s, SCOPES)


def test_split_reads_the_runners_step_and_the_familys_scopes():
    """Another job's step module (not ``jit_train_step``), and a family
    whose program names a scope outside the decoder's: the split follows
    the names ``obs`` carries, and that scope gets its own time."""
    hlo = HLO.replace("jit_train_step", "jit_pipeline_step").replace(
        '"jit(train_step)/optimizer/add"',
        '"jit(pipeline_step)/stage_handoff/collective-permute"')
    trace = recorded()
    trace.modules[0] = [(n.replace("train", "pipeline"), a, d)
                        for n, a, d in trace.modules[0]]
    # a train step module in the slice is not the runner's and counts not
    trace.modules[0].append(("jit_train_step(9)", 5 * MS, 4 * MS))
    family = types.SimpleNamespace(SCOPES=SCOPES + ("stage_handoff",))
    obs = dict(obs_for(trace, hlo), family=family,
               step_module="jit_pipeline_step")
    s = scopes.read(obs)
    assert s["steps"] == 2
    assert s["stage_handoff.fwd"] == pytest.approx(0.002)
    assert "optimizer.fwd" not in s
    assert s["unscoped"] == pytest.approx(0.017)
    assert "stage_handoff 2.000/0.000" in scopes.table(s, family.SCOPES)
    # under the decoder's six names the same op is unscoped
    s = scopes.read(dict(obs_for(trace, hlo),
                         step_module="jit_pipeline_step"))
    assert s["unscoped"] == pytest.approx(0.019)
    # and under the train step's name only the decoy, with no op, is found
    assert scopes.read(dict(obs_for(trace, hlo), family=family)) is None


def test_new_readers_by_hand(mapped):
    read = {m: harness.metric_reader(m).read(mapped) for m in (
        "attention_core_roofline.train", "mlp_roofline.train",
        "lm_head_roofline.train", "optimizer_roofline.train",
        "unscoped_share.train")}
    conf, job = mapped["config"], mapped["traffic"]
    n, pk = CELL.family.dims(conf), peaks.lookup("TPU v5 lite")
    B, S, T = job["batch"], job["seq_len"], job["batch"] * job["seq_len"]
    core = n["L"] * sum(flops.roofline_s(
        *flops.attention_cost(B, S, n["H"], n["K"], n["hd"], backward=b), pk)
        for b in (False, True))
    assert read["attention_core_roofline.train"] == \
        pytest.approx(100 * core / 0.030)
    mlp = flops.roofline_s(*layer_costs.mlp_cost(T, n["d"], n["f"],
                                                 gated=False), pk)
    assert read["mlp_roofline.train"] == \
        pytest.approx(100 * n["L"] * mlp / 0.011)
    head = 6 * T * n["d"] * n["V"] / pk["bf16_flops_per_s"]
    assert read["lm_head_roofline.train"] == pytest.approx(100 * head / 0.012)
    adam = 32 * 125_182_464 / pk["hbm_bytes_per_s"]
    assert read["optimizer_roofline.train"] == \
        pytest.approx(100 * adam / 0.002)
    assert read["unscoped_share.train"] == pytest.approx(100 * 17 / 76)


def test_program_without_scopes_or_a_stale_map_reads_nothing():
    bare = "\n".join(line.split(", metadata=")[0] for line in HLO.splitlines())
    obs = obs_for(recorded(), bare)
    assert scopes.read(obs) is None
    for m in ("attention_core_roofline.train", "mlp_roofline.train",
              "lm_head_roofline.train", "optimizer_roofline.train",
              "unscoped_share.train"):
        assert harness.metric_reader(m).read(obs) is None
    # a map from another compile: the trace's instructions are not in it
    other = HLO.replace("fusion.4 ", "fusion.40 ").replace("dot.2 ", "dot.20 ")
    assert scopes.read(obs_for(recorded(), other)) is None
    # no trace at all (an untraced run): its step's text is never asked for
    untraced = obs_for(recorded())
    del untraced["trace"], untraced["trace_summary"]
    untraced["step_hlo"] = lambda: pytest.fail("compiled without a trace")
    assert scopes.read(untraced) is None
    assert scopes.read({"config": {}, "traffic": {}}) is None


def test_failed_map_reads_nothing_and_does_not_raise():
    def boom():
        raise RuntimeError("no compile")
    obs = dict(obs_for(recorded()), step_hlo=boom)
    assert harness.metric_reader("mlp_roofline.train").read(obs) is None
    assert "scope_map_span_s" in obs


def test_layer_costs_by_hand():
    # MLP over 4 tokens, d 2, f 3: two 2x3 matrices
    fl, by = layer_costs.mlp_cost(4, 2, 3, gated=False)
    assert fl == 3 * 2 * 4 * (2 * 2 * 3)
    assert by == 2 * (2 * 4 * 2 + 12) + 2 * (3 * 4 * 2 + 12) + 4 * 12
    assert layer_costs.mlp_cost(4, 2, 3, gated=True)[0] == 3 * 2 * 4 * 18
    fl, by = layer_costs.lm_head_cost(4, 2, 5)
    assert fl == 6 * 4 * 2 * 5
    assert by == 2 * (2 * 8 + 2 * 10 + 8) + 16 + 4 + 4 * 10
    assert layer_costs.adamw_cost(10) == (0.0, 320.0)
    # opt-125m: embed 50272x768, positions 2048x768, final norm 2x768, and
    # 12 layers of 2 norms (2x768 each), q/k/v/o 4x768x768, q/k/v biases
    # 3x768 and the MLP 2x768x3072
    layer = 2 * 1536 + 4 * 768 * 768 + 3 * 768 + 2 * 768 * 3072
    assert layer_costs.param_count(CELL.family, CELL.config) == \
        50272 * 768 + 2048 * 768 + 1536 + 12 * layer == 125_182_464


def test_compile_reader_counts_step_compiles_after_the_first(monkeypatch):
    import jax
    import jax.numpy as jnp
    from repro.obs import compiles
    reader = harness.metric_reader("compiles.train")
    compiles.install()
    monkeypatch.setattr(compiles, "_events", [])     # this test's alone
    assert reader.read({}) is None                   # the step never ran

    def train_step(x):
        return x + 1
    step = jax.jit(train_step)
    step(jnp.ones(3))
    step(jnp.ones(3))
    assert reader.read({}) == 0.0
    t0 = compiles.events()[-1].end_s
    step(jnp.ones(4))                                # compiles again
    assert reader.read({}) == 2.0
    # a compile inside the scope readers' own map is left out
    t1 = compiles.events()[-1].end_s
    assert reader.read({"scope_map_span_s": (t0 + 1e-9, t1)}) == 0.0
