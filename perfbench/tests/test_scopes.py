"""The join from a device op to the program's named scopes: ``op_name`` forms,
the instruction -> ``op_name`` table of a compiled text, and the shares the
eight ``*_time_pct`` readers report, on hand-made events and on a step
recorded on the v5e."""

import gzip
import importlib
import json
import os

import pytest

from perfbench.harness import scopes
from perfbench.harness import trace_reduce as tr
from perfbench.harness.scopes import Scope
from perfbench.harness.trace_reduce import Event, Trace

STEP = "jit(train_step)/while/body/closed_call"
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


@pytest.mark.parametrize("op_name, expected", [
    # not differentiated: plain
    (f"{STEP}/embed/dropout/jit(_bernoulli)/lt", Scope("embed", "forward", False, True)),
    ("jit(train_step)/optimizer/mul", Scope("optimizer", "optimizer", False, False)),
    # forward and backward of a differentiated scope
    (f"{STEP}/jvp(mlp)/bsd,df->bsf/dot_general", Scope("mlp", "forward", False, False)),
    (f"{STEP}/transpose(jvp(mlp))/bsf,fd->bsd/dot_general",
     Scope("mlp", "backward", False, False)),
    # a custom_vjp's backward rule, and an inner scope of a wrapped one
    (f"{STEP}/transpose(jvp(attention))/jit(flash_attention)/pallas_call",
     Scope("attention", "backward", False, False)),
    (f"{STEP}/transpose(jvp(mlp))/dropout/div", Scope("mlp", "backward", False, True)),
    # inside a scan body the wrapper sits above the loop
    ("jit(train_step)/transpose(jvp())/while/body/closed_call/attention/mul",
     Scope("attention", "backward", False, False)),
    # remat: the backward proper, and what runs a second time
    ("jit(train_step)/transpose(jvp(jvp()))/checkpoint/mlp/mul",
     Scope("mlp", "backward", False, False)),
    ("jit(train_step)/transpose(jvp(jvp()))/checkpoint/rematted_computation/attention/"
     "jit(flash_attention)/shard_map/pallas_call", Scope("attention", "backward", True, False)),
    # the outermost module counts; a jitted function of a scope's name is no scope
    (f"{STEP}/jvp(head)/loss/exp", Scope("head", "forward", False, False)),
    (f"{STEP}/jvp()/jit(mlp)/add", Scope(None, "forward", False, False)),
    # several paths: the first that names a module
    ("jit(train_step)/squeeze;jit(train_step)/transpose(jvp(loss))/reshape",
     Scope("loss", "backward", False, False)),
    ("jit(train_step)/jvp(attention)/reshape;jit(train_step)/transpose(jvp(mlp))/squeeze",
     Scope("attention", "forward", False, False)),
    # no scope at all
    ("jit(train_step)/while/body/dynamic_slice", Scope(None, "forward", False, False)),
    ("jit(train_step)/transpose(jvp())/pad", Scope(None, "backward", False, False)),
    ("", Scope(None, "forward", False, False)),
])
def test_classify(op_name, expected):
    assert scopes.classify(op_name) == expected


HLO_TEXT = """HloModule jit_train_step, entry_computation_layout={(f32[8,128]{1,0})->f32[8,128]{1,0}}

%fused_computation.7 (param_0: f32[8,128]) -> f32[8,128] {
  %param_0 = f32[8,128]{1,0} parameter(0)
  %convert.3 = bf16[8,128]{1,0} convert(%param_0), metadata={op_name="jit(train_step)/jvp(mlp)/convert_element_type"}
  ROOT %convolution.1 = f32[8,128]{1,0} convolution(%convert.3, %convert.3), dim_labels=bf_io->bf, metadata={op_type="dot_general" op_name="jit(train_step)/transpose(jvp(mlp))/bsf,fd->bsd/dot_general" source_file="tinygpt.py" source_line=770}
}

%fused_computation.8 (param_0.1: f32[8,128]) -> f32[8,128] {
  %param_0.1 = f32[8,128]{1,0} parameter(0)
  %add.1 = f32[8,128]{1,0} add(%param_0.1, %param_0.1), metadata={op_name="jit(train_step)/optimizer/add"}
  ROOT %bitcast.2 = f32[8,128]{1,0} bitcast(%add.1)
}

ENTRY %main.9 (p.1: f32[8,128]) -> f32[8,128] {
  %p.1 = f32[8,128]{1,0} parameter(0)
  %fusion.1 = f32[8,128]{1,0} fusion(%p.1), kind=kOutput, calls=%fused_computation.7
  %flash.7 = f32[8,128]{1,0} custom-call(%fusion.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/jvp(attention)/jit(flash_attention)/pallas_call"}
  %copy-start.1 = (f32[8,128]{1,0}, f32[8,128]{1,0}, u32[]) copy-start(%flash.7)
  ROOT %add_fusion.2 = f32[8,128]{1,0} fusion(%flash.7), kind=kLoop, calls=%fused_computation.8
}
"""


def test_op_names_of_a_compiled_text():
    names = scopes.op_names(HLO_TEXT)
    assert names["flash.7"].endswith("pallas_call")
    # a fusion without metadata of its own: its computation's ROOT, ...
    assert names["fusion.1"] == "jit(train_step)/transpose(jvp(mlp))/bsf,fd->bsd/dot_general"
    # ... or, where the ROOT has none either, the first instruction there that has
    assert names["add_fusion.2"] == "jit(train_step)/optimizer/add"
    assert "copy-start.1" not in names and "p.1" not in names
    assert names["convert.3"] == "jit(train_step)/jvp(mlp)/convert_element_type"
    assert scopes.op_names("") == {} and scopes.op_names("not an HLO text\n") == {}


def op(name, start, end):
    return Event(f"%{name} = f32[8,128]{{1,0}} fusion(%p.1), kind=kLoop", start, end)


def hand_made():
    """One chip: a while [2, 12] that holds a backward MLP matmul, a forward
    kernel and a gap; then an async copy nothing names and the optimizer."""
    ops = [op("while.1", 2.0, 12.0), op("fusion.1", 2.0, 6.0), op("flash.7", 6.0, 9.0),
           op("copy-start.1", 12.0, 14.0), op("add_fusion.2", 14.0, 18.0)]
    return Trace({"/device:TPU:0": {tr.OPS_LINE: ops}})


def test_scope_seconds_counts_a_while_once():
    seconds, busy, unscoped = scopes.scope_seconds(hand_made(), "/device:TPU:0", HLO_TEXT)
    assert busy == pytest.approx(16.0)  # = the busy union: the while's own 3 s once
    assert seconds[Scope("mlp", "backward", False, False)] == pytest.approx(4.0)
    assert seconds[Scope("attention", "forward", False, False)] == pytest.approx(3.0)
    assert seconds[Scope("optimizer", "optimizer", False, False)] == pytest.approx(4.0)
    assert unscoped == {"while": pytest.approx(3.0), "copy-start": pytest.approx(2.0)}


METRICS = {"backward_time_pct": 25.0, "optimizer_time_pct": 25.0, "recompute_time_pct": 0.0,
           "attention_time_pct": 18.75, "mlp_time_pct": 25.0, "head_loss_time_pct": 0.0,
           "dropout_time_pct": 0.0, "unscoped_time_pct": 31.25}


def read(metric, trace, hlo_text):
    reader = importlib.import_module(f"perfbench.metrics.{metric}").read
    return reader(trace, {"hlo_text": hlo_text})


def test_the_readers_share_one_reduction_and_sum_to_100(capsys):
    trace = hand_made()
    values = {m: read(m, trace, HLO_TEXT) for m in METRICS}
    assert values == {m: pytest.approx(v) for m, v in METRICS.items()}
    modules = ("attention", "mlp", "head_loss", "optimizer", "unscoped")  # no embed op here
    assert sum(values[f"{m}_time_pct"] for m in modules) == pytest.approx(100.0)
    lines = [l for l in capsys.readouterr().out.splitlines() if "perfbench: scopes:" in l]
    assert len(lines) == 1  # one line a run, whichever reader comes first
    assert "mlp.backward 4.0000" in lines[0] and "['while', 3.0]" in lines[0]


@pytest.mark.parametrize("metric", sorted(METRICS))
def test_a_program_without_scopes_reports_nothing(metric):
    """The parent of PR 24, or a text no ``op_name`` can be read from: the
    metric is absent, and nothing raises."""
    unnamed = HLO_TEXT.replace("mlp", "x").replace("attention", "y").replace("optimizer", "z")
    assert read(metric, hand_made(), unnamed) is None
    assert read(metric, hand_made(), "") is None
    assert read(metric, Trace({}), HLO_TEXT) is None


# --- a step of tinygpt-a.seq2048 recorded on the v5e with its op_names
#     (tools/record_scoped_trace.py, PR 24) ---


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(os.path.join(DATA, "trace_scoped.json.gz"), "rt") as f:
        record = json.load(f)
    trace = Trace({record["plane"]: {tr.OPS_LINE: [Event(*row) for row in record["ops"]]}})
    return trace, record["hlo_text"], record


def test_recorded_trace_gives_what_was_recorded(recorded):
    trace, hlo_text, record = recorded
    seconds, busy, unscoped = scopes.scope_seconds(trace, record["plane"], hlo_text)
    assert busy == pytest.approx(record["expected"]["busy_s"], rel=1e-9)
    # self times: the micro-batch loop holds nearly every op and is counted once
    assert busy == pytest.approx(tr.total(tr.merge((e.start, e.end)
                                                   for e in trace.ops(record["plane"]))), rel=1e-6)
    for *scope, expected in record["expected"]["scopes"]:
        assert seconds[Scope(*scope)] == pytest.approx(expected, rel=1e-9)
    assert unscoped.most_common(1)[0][0] == record["expected"]["unscoped"][0][0]


def test_recorded_trace_is_the_step_it_is_known_to_be(recorded):
    """16 layers x 4 micro-batches: 64 forward kernels, all under ``attention``
    and none backward (XLA einsums below 4096); dropout 0.1 shows; no remat;
    the names reach all but the accumulation adds and the compiler's copies."""
    trace, hlo_text, record = recorded
    names = scopes.op_names(hlo_text)
    kernels = [e for e in trace.ops(record["plane"])
               if "pallas_call" in names.get(scopes.instruction_name(e), "")]
    assert len(kernels) == 64
    assert {scopes.classify(names[scopes.instruction_name(e)]) for e in kernels} == {
        Scope("attention", "forward", False, False)}
    share = {m: read(f"{m}_time_pct", trace, hlo_text) for m in (
        "attention", "mlp", "head_loss", "optimizer", "unscoped", "backward", "dropout",
        "recompute")}
    kernel_share = 100.0 * sum(e.end - e.start for e in kernels) / record["expected"]["busy_s"]
    assert share["attention"] > kernel_share > 10.0
    assert 40 < share["attention"] < 60 and 20 < share["mlp"] < 35
    assert 3 < share["optimizer"] < 9 and 45 < share["backward"] < 65
    assert 0 < share["dropout"] < 5 and share["recompute"] == 0
    assert share["unscoped"] < 15
    embed = 100.0 - sum(share[m] for m in ("attention", "mlp", "head_loss", "optimizer",
                                           "unscoped"))
    assert 0 < embed < 2  # the one module with no metric of its own
