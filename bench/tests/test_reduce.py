"""The reduction from a profiler trace to per-layer metrics, on hand-built
traces, and the model-FLOPs count against a count by hand."""
import dataclasses
import math

import pytest

from bench import flops as F
from bench import harness as H
from bench import trace as T

MS = 1_000_000_000          # picoseconds per millisecond


def _xspace(devices, host, window):
    """Text proto of an XSpace: ``devices`` is a list of (ops, modules), each
    a list of (name, start_ms, dur_ms); ``host`` a list of loop spans."""
    def plane(pid, name, lines):
        meta, out, ids = [], [], {}
        for lid, (lname, events) in enumerate(lines, 1):
            evs = []
            for n, start, dur in events:
                mid = ids.setdefault(n, len(ids) + 1)
                evs.append(f"events {{ metadata_id: {mid} offset_ps: {int(start * MS)} "
                           f"duration_ps: {int(dur * MS)} }}")
            out.append(f'lines {{ id: {lid} name: "{lname}" timestamp_ns: 0 {" ".join(evs)} }}')
        for n, mid in ids.items():
            meta.append(f'event_metadata {{ key: {mid} value {{ id: {mid} name: "{n}" }} }}')
        return f'planes {{ id: {pid} name: "{name}" {" ".join(out + meta)} }}'
    planes = [plane(i + 1, f"/device:TPU:{i}", [("XLA Ops", ops), ("XLA Modules", mods)])
              for i, (ops, mods) in enumerate(devices)]
    planes.append(plane(99, "/host:CPU", [("python", host + [("traced_window",) + window])]))
    return "\n".join(planes)


def _trace(devices, host=(), window=(0.0, 10.0)):
    """``window`` is the traced span's (start_ms, dur_ms)."""
    from jax.profiler import ProfileData
    return T.from_profile(ProfileData.from_text_proto(_xspace(devices, list(host), window)))


# two steps of 4 ms, each a module with a fusion, an all-to-all and an
# all-reduce; 1 ms idle between them while the host waited for a batch
STEP_OPS = [("fusion.1", 0.0, 2.0), ("all-to-all.3", 2.0, 1.0), ("all-reduce.7", 3.0, 1.0),
            ("fusion.1", 5.0, 2.0), ("all-to-all.3", 7.0, 1.0), ("all-reduce.7", 8.0, 1.0)]
STEP_MODS = [("jit_train_step_fn(12)", 0.0, 4.0), ("jit_train_step_fn(12)", 5.0, 4.0)]
HOST = [("dispatch", 0.0, 0.5), ("batch_wait", 4.0, 1.0), ("fetch_metrics", 9.0, 1.0)]


def _steps(n, offset=0.0, module="jit_train_step_fn(12)"):
    """``n`` steps like the two above, every 5 ms from ``offset``."""
    ops, mods = [], []
    for i in range(n):
        t = offset + 5.0 * i
        ops += [(name, t + a, d) for name, a, d in STEP_OPS[:3]]
        mods.append((module, t, 4.0))
    return ops, mods


def test_busy_union_and_idle_share():
    # overlapping ops count once; the window clips what lies outside it
    tr = _trace([([("a", 1.0, 2.0), ("b", 2.0, 2.0), ("c", 8.0, 4.0)], [])],
                window=(0.0, 10.0))
    assert T.busy_s(tr.devices[0], tr.window) == pytest.approx(5e-3)
    assert T.idle_gaps(tr.devices[0], tr.window) == pytest.approx(
        [(0.0, 1e-3), (4e-3, 8e-3)])
    view = H.RunView(trace=tr)
    assert H.metric_modules()["device_idle"].read(view) == pytest.approx(50.0)


def test_collective_sums_by_op_kind():
    tr = _trace([_steps(5), _steps(5)], window=(-1.0, 31.0))
    T.trim_to_steps(tr, "jit_train_step_fn")
    assert [d.steps for d in tr.devices] == [3, 3]
    view = H.RunView(trace=tr)
    mods = H.metric_modules()
    assert mods["exchange_ms"].read(view) == pytest.approx(1.0)
    assert mods["grad_sync_ms"].read(view) == pytest.approx(1.0)
    assert T.opcode("all-reduce-start.4") == "all-reduce-start"
    assert T.op_seconds(tr.devices[0], tr.devices[0].window, ("fusion",)) == pytest.approx(6e-3)


def test_no_collectives_reads_nothing():
    ops = [("fusion.1", 5.0 * i, 4.0) for i in range(4)]
    tr = _trace([(ops, [("jit_step(1)", a, d) for _, a, d in ops])], window=(-1.0, 31.0))
    T.trim_to_steps(tr, "jit_step")
    assert tr.devices[0].steps == 2
    view = H.RunView(trace=tr)
    mods = H.metric_modules()
    assert mods["exchange_ms"].read(view) is None
    assert mods["grad_sync_ms"].read(view) is None


def test_step_module_found_by_name():
    ops, mods = _steps(5)
    other = [("jit_train_step_fn_other(3)", 1.0, 9.0), ("jit__norms(4)", 9.0, 1.0)]
    tr = _trace([(ops, mods + other)], window=(-1.0, 31.0))
    T.trim_to_steps(tr, "jit_train_step_fn")
    assert tr.devices[0].steps == 3
    view = H.RunView(trace=tr, step_module="jit_train_step_fn")
    assert H.metric_modules()["step_device_ms"].read(view) == pytest.approx(4.0)
    tr = _trace([(ops, mods + other)], window=(-1.0, 31.0))
    T.trim_to_steps(tr, "jit_absent")
    view = H.RunView(trace=tr, step_module="jit_absent")
    assert H.metric_modules()["step_device_ms"].read(view) is None


def test_each_device_counts_its_own_whole_steps():
    """Each device's clock is its own: device 1 runs the same steps 0.7 ms
    later, and the profiler cut its first and last module events short.
    Each device counts its own whole steps, start to start, so the step
    time, the idle share and the rate are the same on both."""
    ops0, mods0 = _steps(6)
    ops1, mods1 = _steps(6, offset=0.7)
    mods1[0] = (mods1[0][0], 2.0, 2.7)                  # cut by the profiler
    mods1.append((mods1[0][0], 30.0, 0.05))              # cut where it stopped
    tr = _trace([(ops0, mods0), (ops1, mods1)], window=(-0.5, 31.0))
    T.trim_to_steps(tr, "jit_train_step_fn")
    assert [d.steps for d in tr.devices] == [4, 4]
    assert tr.devices[0].window == pytest.approx((5e-3, 25e-3))
    assert tr.devices[1].window == pytest.approx((5.7e-3, 25.7e-3))
    view = H.RunView(trace=tr, step_module="jit_train_step_fn")
    mods = H.metric_modules()
    assert mods["step_device_ms"].read(view) == pytest.approx(4.0)
    assert mods["device_idle"].read(view) == pytest.approx(20.0)
    assert T.traced_steps_per_s(tr) == pytest.approx(200.0)


def test_mfu_reads_the_device_trace_not_host_completions():
    """The traced rate is the device's whole steps over their window: the
    host's loss fetches, bunched when the profiler starts, do not move it."""
    cell = H.load_cell("smile-3.7b.mlm512.4chip")
    pk = {"bf16_flops": 197e12}
    want = (100.0 * F.train_flops_per_token(cell.config, cell.seq)
            * cell.tokens_per_step * 200.0 / (4 * pk["bf16_flops"]))
    for done in ([0.010, 0.015, 0.020, 0.025], [0.010, 0.0101, 0.0102, 0.025]):
        tr = _trace([_steps(6, offset=0.1 * i) for i in range(4)], window=(-0.5, 31.0))
        device = {}
        metrics, _ = H.per_layer_metrics(
            cell, tr, {"done": done, "wait": 0.0}, "jit_train_step_fn",
            H.metric_modules(), pk, device, log=lambda s: None)
        assert metrics["mfu"]["value"] == pytest.approx(want)
        assert metrics["step_device_ms"]["value"] == pytest.approx(4.0)
        assert device["window_s"] == pytest.approx(20e-3)
        assert device["busy_s"] == pytest.approx(16e-3)


def test_breakdown_names_idle_gaps_by_host_span():
    tr = _trace([(STEP_OPS, STEP_MODS)], host=HOST)
    bd = T.breakdown(tr)
    assert bd["device_ops"][0] == ["fusion.1", pytest.approx(4e-3)]
    assert [g[0] for g in bd["idle_gaps"]] == ["batch_wait", "fetch_metrics"]
    assert bd["idle_gaps"][0][1] == pytest.approx(1e-3)


def test_trace_without_window_span_is_refused():
    from jax.profiler import ProfileData
    txt = _xspace([(STEP_OPS, STEP_MODS)], [], (0.0, 1.0)).replace("traced_window", "other")
    with pytest.raises(ValueError):
        T.from_profile(ProfileData.from_text_proto(txt))


def test_mfu_from_rate_and_peak():
    mfu = H.metric_modules()["mfu"]
    view = H.RunView(peak={"bf16_flops": 100.0}, traced_tokens_per_s=2.0,
                     flops_per_token=10.0, chips=2)
    assert mfu.read(view) == pytest.approx(10.0)
    assert mfu.read(H.RunView(peak=None, traced_tokens_per_s=2.0,
                              flops_per_token=10.0, chips=2)) is None


def test_model_flops_by_hand_for_the_smile_cut():
    cfg = H.load_cell("smile-3.7b.mlm512").config
    d, H_, f, V, S = 768, 12, 3072, 32128, 512
    attn = 2 * (4 * d * d) + 2 * (2 * S * d)      # q, k, v, o; scores and values
    layer_dense = attn + 2 * (2 * d * f)
    layer_moe = attn + 2 * (2 * d * f) + 2 * d * (2 + 64)
    head = 2 * d * V
    assert F.forward_flops_per_token(cfg, S) == layer_dense + layer_moe + head
    assert F.train_flops_per_token(cfg, S) == 3 * (layer_dense + layer_moe + head)
    assert F.train_flops_per_token(cfg, S) == 242_721_792


def test_switch_router_flops():
    cfg = H.load_cell("switch-3.7b.mlm512").config
    smile = H.load_cell("smile-3.7b.mlm512").config
    assert (F.forward_flops_per_token(cfg, 512) - F.forward_flops_per_token(smile, 512)
            == 2 * 768 * (128 - 66))


def test_per_step_time_spans():
    assert H.spans([1.0, 2.0, 3.5, 4.0], 0.0, 1) == [1.0, 1.0, 1.5, 0.5]
    assert H.spans([1.0, 2.0, 3.5, 4.0], 0.0, 2) == [1.0, 1.25, 1.0]
    assert math.isclose(H.p95([float(i) for i in range(101)]), 95.0)


@pytest.mark.parametrize("name,want", [
    ("smile-3.7b.mlm512", 242_721_792),
    ("switch-3.7b.mlm512", 243_007_488),
    ("smile-3.7b.mlm512.4chip", 242_721_792),
])
def test_flops_per_token_of_the_cells_as_pinned(name, want):
    """The configurations' reference gives no count, so ``mfu`` reads the
    MLM encoder's, as before the reference could give one."""
    cell = H.load_cell(name)
    assert H.train_flops_per_token(cell.config, cell.seq) == want


def test_a_reference_that_counts_its_flops_is_used(monkeypatch):
    import sys
    import types
    from bench.references import mlm_moe
    ref = types.ModuleType("bench.references.counting")
    ref.__dict__.update({k: v for k, v in vars(mlm_moe).items()
                         if not k.startswith("__")})
    ref.train_flops_per_token = lambda cfg, seq: 7 * seq
    monkeypatch.setitem(sys.modules, "bench.references.counting", ref)
    cell = H.load_cell("smile-3.7b.mlm512")
    cfg = dict(cell.config, reference="counting")
    assert H.train_flops_per_token(cfg, 512) == 3584
    tr = _trace([_steps(6)], window=(-0.5, 31.0))
    metrics, _ = H.per_layer_metrics(
        dataclasses.replace(cell, config=cfg), tr, {"done": [], "wait": 0.0},
        "jit_train_step_fn", H.metric_modules(), {"bf16_flops": 197e12}, {},
        log=lambda s: None)
    assert metrics["mfu"]["value"] == pytest.approx(
        100.0 * 3584 * cell.tokens_per_step * 200.0 / 197e12)
