"""Device time by the program's named scopes, on hand-built traces and
``op_scopes`` maps: reading an ``op_name``, mapping a compiled module's
instructions to theirs, and counting each stretch of device time once."""
import pytest

from bench import harness as H
from bench import scopes as S
from bench import trace as T
from bench.tests.test_reduce import _steps, _trace

JIT = "jit(train_step_fn)"


@pytest.mark.parametrize("op_name,want", [
    (f"{JIT}/jvp(embed)/jit(_take)/gather", ("embed", None)),
    (f"{JIT}/transpose(jvp(lm_head))/dot_general", ("lm_head", None)),
    (f"{JIT}/transpose(jvp())/while/body/closed_call/checkpoint/"
     "rematted_computation/attention/dot_general", ("attention", None)),
    (f"{JIT}/shard_map/grad_sync/psum", ("grad_sync", None)),
    (f"{JIT}/shard_map/optimizer/jit(clip)/max", ("optimizer", None)),
    (f"{JIT}/jvp()/while/body/closed_call/moe/hop0/dispatch/"
     "jit(searchsorted)/vmap()/while/body/add", ("moe", "dispatch")),
    (f"{JIT}/transpose(jvp(moe))/hop0/route/psum", ("moe", "route")),
    (f"{JIT}/vmap(ffn)/mul", ("ffn", None)),
    (f"{JIT}/jvp()/while/body/max", None),
    (f"{JIT}/jit(attention)/dot_general", None),      # a function, not a scope
    ("params['embed']['table']", None),
    ("", None),
    (None, None),
])
def test_scope_of_strips_transform_wrappers(op_name, want):
    assert S.scope_of(op_name) == want


def test_nested_hops_resolve_to_the_innermost_phase_and_hop():
    nested = (f"{JIT}/transpose(jvp())/while/body/closed_call/checkpoint/"
              "moe/hop0/hop1/expert_ffn/gtd,gdf->gtf/dot_general")
    assert S.scope_of(nested) == ("moe", "expert_ffn")
    assert S.hop_of(nested) == "hop1"
    outer = f"{JIT}/jvp()/while/body/closed_call/moe/hop0/exchange/all_to_all"
    assert S.scope_of(outer) == ("moe", "exchange")
    assert S.hop_of(outer) == "hop0"
    # the outermost layer wins: a block nested in the head stays the head's
    assert S.scope_of(f"{JIT}/lm_head/attention/dot_general") == ("lm_head", None)
    assert S.hop_of(f"{JIT}/optimizer/mul") is None


def test_op_scopes_reads_every_computation():
    text = "\n".join([
        "%fused_computation.1 (param_0: f32[4]) -> f32[4] {",
        '  %multiply.3 = f32[4]{0} multiply(%param_0, %param_0), '
        'metadata={op_name="jit(f)/optimizer/mul" source_line=3}',
        "}",
        "%region_0.9 (arg: (s32[], f32[4])) -> (s32[], f32[4]) {",
        '  %all-reduce.19 = (f32[4]{0}, f32[2]{0}) all-reduce(%a, %b), '
        'replica_groups={{0,1}}, metadata={op_name="jit(f)/grad_sync/psum"}',
        "}",
        "ENTRY %main.4 (p: f32[4]) -> f32[4] {",
        '  %p = f32[4]{0} parameter(0), metadata={op_name="params[\\\'w\\\']"}',
        "  %copy.2 = f32[4]{0} copy(%p)",
        '  ROOT %fusion.52 = f32[4]{0} fusion(%copy.2), kind=kLoop, '
        'calls=%fused_computation.1, metadata={op_name="jit(f)/optimizer/mul"}',
        "}",
    ])
    got = S.op_scopes(text)
    assert got == {"multiply.3": "jit(f)/optimizer/mul",
                   "all-reduce.19": "jit(f)/grad_sync/psum",
                   "p": "params[\\'w\\']",
                   "fusion.52": "jit(f)/optimizer/mul"}
    assert "copy.2" not in got


# the two-step trace of test_reduce: per step a fusion (2 ms), an
# all-to-all (1 ms) and an all-reduce (1 ms)
SCOPES = {"fusion.1": f"{JIT}/optimizer/mul",
          "all-to-all.3": f"{JIT}/jvp()/while/body/moe/hop0/exchange/all_to_all",
          "all-reduce.7": f"{JIT}/shard_map/grad_sync/psum"}


def _five_steps(devices=2):
    tr = _trace([_steps(5) for _ in range(devices)], window=(-1.0, 31.0))
    T.trim_to_steps(tr, "jit_train_step_fn")
    return tr


def test_scope_ms_per_whole_step():
    tr = _five_steps()
    assert [d.steps for d in tr.devices] == [3, 3]
    assert S.scope_ms(tr, SCOPES, S.in_layers("optimizer")) == pytest.approx(2.0)
    assert S.scope_ms(tr, SCOPES, S.in_layers("moe")) == pytest.approx(1.0)
    assert S.scope_ms(tr, SCOPES, S.in_layers("embed", "lm_head")) == 0.0
    tr.devices[0].step_events = []
    tr.devices[1].step_events = []
    assert S.scope_ms(tr, SCOPES, S.in_layers("moe")) is None


def test_each_stretch_of_device_time_counts_once():
    """An op event that encloses others (a loop around its body) keeps only
    the time no inner op covers, so the split adds up to the busy time."""
    ops = [("while.5", 0.0, 4.0), ("fusion.1", 0.5, 1.0), ("fusion.2", 2.0, 1.0),
           ("copy.9", 4.0, 1.0)]
    got = dict(S.self_times([(n, a * 1e-3, (a + d) * 1e-3) for n, a, d in ops],
                            (0.0, 1.0)))
    assert got == pytest.approx({"while.5": 2e-3, "fusion.1": 1e-3,
                                 "fusion.2": 1e-3, "copy.9": 1e-3})
    mods = [("jit_train_step_fn(1)", 10.0 * i, 5.0) for i in range(4)]
    steps = [(n, a + 10.0 * i, d) for i in range(4) for n, a, d in ops]
    tr = _trace([(steps, mods)], window=(-1.0, 41.0))
    T.trim_to_steps(tr, "jit_train_step_fn")
    scopes = {"while.5": f"{JIT}/jvp()/while", "fusion.1": f"{JIT}/jvp(attention)/add",
              "fusion.2": f"{JIT}/transpose(jvp(moe))/hop0/combine/add"}
    (dev,) = S.split_ms(tr, scopes)
    assert dev["steps"] == 2
    assert dev["layers_ms"]["attention"] == pytest.approx(1.0)
    assert dev["layers_ms"]["moe"] == pytest.approx(1.0)
    # the loop's own 2 ms and the copy, which has no metadata
    assert dev["layers_ms"][S.UNATTRIBUTED] == pytest.approx(3.0)
    assert sum(dev["layers_ms"].values()) == pytest.approx(dev["busy_ms"])
    assert dev["moe_phases_ms"] == pytest.approx({"hop0/combine": 1.0})
    assert dev["top_ops_ms"][S.UNATTRIBUTED] == [["while.5", pytest.approx(2.0)],
                                                ["copy.9", pytest.approx(1.0)]]


def test_ops_missing_from_the_map_are_unattributed():
    tr = _five_steps(devices=1)
    partial = {k: v for k, v in SCOPES.items() if k != "fusion.1"}
    (dev,) = S.split_ms(tr, partial)
    assert dev["layers_ms"]["optimizer"] == 0.0
    assert dev["layers_ms"][S.UNATTRIBUTED] == pytest.approx(2.0)
    assert dev["layers_ms"]["moe"] == pytest.approx(1.0)
    assert dev["layers_ms"]["grad_sync"] == pytest.approx(1.0)
    assert S.scope_ms(tr, {}, lambda name, op: S.scope_of(op) is None) == pytest.approx(4.0)


def test_grad_allreduce_counts_all_reduces_under_grad_sync_alone():
    tr = _five_steps()
    assert S.scope_ms(tr, SCOPES, S.grad_allreduce) == pytest.approx(1.0)
    # the same all-reduce under the head's loss sum is an activation sum
    head = dict(SCOPES, **{"all-reduce.7": f"{JIT}/shard_map/jvp(lm_head)/psum"})
    assert S.scope_ms(tr, head, S.grad_allreduce) == 0.0
    # a fusion under grad_sync is not an all-reduce
    fused = dict(SCOPES, **{"fusion.1": f"{JIT}/shard_map/grad_sync/add"})
    assert S.scope_ms(tr, fused, S.grad_allreduce) == pytest.approx(1.0)
    (dev, _) = S.split_ms(tr, SCOPES)
    assert dev["grad_allreduce_ms"] == pytest.approx(1.0)
    assert dev["grad_allreduce_ms"] <= dev["all_reduce_ms"]


def test_existing_readers_read_what_they_read():
    """The readers of the accepted metrics read the same trace as before,
    beside the scopes' split of it."""
    tr = _five_steps()
    view = H.RunView(trace=tr, step_module="jit_train_step_fn")
    mods = H.metric_modules()
    assert mods["exchange_ms"].read(view) == pytest.approx(1.0)
    assert mods["grad_sync_ms"].read(view) == pytest.approx(1.0)
    assert mods["step_device_ms"].read(view) == pytest.approx(4.0)
    assert mods["device_idle"].read(view) == pytest.approx(20.0)
    for dev in S.split_ms(tr, SCOPES):
        assert sum(dev["layers_ms"].values()) == pytest.approx(dev["step_module_ms"])


# one step: each op's scope (as its HLO metadata gives it) and device time
READER_OPS = [("fusion.1", "shard_map/optimizer/mul", 2.0),
              ("fusion.2", "transpose(jvp())/while/body/checkpoint/attention/dot_general", 1.5),
              ("all-to-all.3", "jvp()/while/body/moe/hop0/exchange/all_to_all", 1.0),
              ("fusion.4", "jvp(embed)/jit(_take)/gather", 0.25),
              ("fusion.5", "transpose(jvp(lm_head))/dot_general", 0.5),
              ("copy.6", None, 0.25)]
READER_HLO = "\n".join(
    ["ENTRY %main.1 (p: f32[4]) -> f32[4] {"]
    + [f"  %{name} = f32[4]{{0}} fusion(%p), kind=kLoop, "
       f'metadata={{op_name="{JIT}/{op}" source_line=1}}'
       for name, op, _ in READER_OPS if op is not None]
    + ["  ROOT %copy.6 = f32[4]{0} copy(%p)", "}"])


def _reader_trace(devices=2):
    ops, mods = [], []
    for i in range(5):
        t = 7.0 * i
        mods.append(("jit_train_step_fn(12)", t, 5.5))
        for name, _, dur in READER_OPS:
            ops.append((name, t, dur))
            t += dur
    tr = _trace([(ops, mods)] * devices, window=(-1.0, 36.0))
    T.trim_to_steps(tr, "jit_train_step_fn")
    return tr


@pytest.mark.parametrize("reader,want", [
    ("optimizer_ms", 2.0), ("attention_ms", 1.5), ("moe_ms", 1.0),
    ("lm_head_ms", 0.75)])
def test_scope_readers_from_trace_and_hlo_text(reader, want):
    tr = _reader_trace()
    assert [d.steps for d in tr.devices] == [3, 3]
    mod = H.metric_modules()[reader]
    view = H.RunView(trace=tr, op_scopes=S.op_scopes(READER_HLO))
    assert mod.read(view) == pytest.approx(want)
    assert mod.read(H.RunView(trace=tr)) is None


def test_scope_readers_sum_within_the_step():
    """The four readers add up to the step less the ops of no scope."""
    tr = _reader_trace()
    metrics, _ = H.per_layer_metrics(
        H.load_cell("smile-3.7b.mlm512"), tr, {"done": [], "wait": 0.0},
        "jit_train_step_fn", H.metric_modules(), None, {}, log=lambda s: None,
        op_scopes=S.op_scopes(READER_HLO))
    four = sum(metrics[k]["value"] for k in
               ("optimizer_ms", "attention_ms", "moe_ms", "lm_head_ms"))
    assert four == pytest.approx(metrics["step_device_ms"]["value"] - 0.25)
    metrics, _ = H.per_layer_metrics(
        H.load_cell("smile-3.7b.mlm512"), tr, {"done": [], "wait": 0.0},
        "jit_train_step_fn", H.metric_modules(), None, {}, log=lambda s: None)
    assert not {"optimizer_ms", "attention_ms", "moe_ms", "lm_head_ms"} & set(metrics)
