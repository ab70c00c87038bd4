"""Device time credited to the program's named scopes: the crediting on
small synthetic traces, the scopes in the compiled programs of both
admitted cells (a refactor that drops one would zero its metric), and
the program's spans in a recorded profiler trace."""
import os
import time

import pytest

import harness
import scopes
import test_bench_cells as cells
import tracing


@pytest.mark.parametrize("op_name, scope", [
    # a fusion inside the sweep's nested while bodies
    ("jit(fit_cd_tol)/while/body/while/body/closed_call/cd.stats/exp",
     "cd.stats"),
    # backward of recomputed forward work, inside the layer scan
    ("jit(train_step)/while/body/checkpoint/transpose(jvp(ssm.ssd))/mul",
     "ssm.ssd"),
    ("jit(train_step)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "rematted_computation/ssm.ssd/jit(softplus)/exp", "ssm.ssd"),
    ("jit(train_step)/jvp(jit(cox_loss))/cox.head/mul", "cox.head"),
    # innermost scope wins
    ("jit(f)/cd.stats/cd.update/add", "cd.update"),
    ("jit(f)/optim.adamw/transpose(model.norm)/mul", "model.norm"),
    # whole parts only
    ("jit(f)/ssm.ssd_extra/mul", scopes.UNSCOPED),
    ("jit(f)/my.cd.stats/mul", scopes.UNSCOPED),
    ("jit(fit_cd_tol)/while/body/while/body/dynamic_slice",
     scopes.UNSCOPED),
    ("", scopes.UNKNOWN),
    (None, scopes.UNKNOWN),
])
def test_scope_of_an_op_name(op_name, scope):
    assert scopes.scope_of(op_name) == scope


def test_transform_wrappers_are_stripped():
    assert scopes.parts("jit(step)/checkpoint/transpose(jvp(ssm.ssd))/mul") \
        == ["step", "checkpoint", "ssm.ssd", "mul"]


HLO = """HloModule jit_fit_cd_tol, entry_computation_layout={(f32[8])->f32[]}

%fused_computation.1 (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  ROOT %exponential.1 = f32[8]{0} exponential(f32[8]{0} %param_0), \
metadata={op_name="jit(fit_cd_tol)/while/body/cd.stats/exp"}
}

ENTRY %main (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  %reduce-window.60 = f32[8]{0} reduce-window(%p, %c), window={size=8}, \
to_apply=%region_4.7
  %add_bitcast_fusion.8 = f32[8]{0} fusion(%reduce-window.60), \
kind=kLoop, calls=%fused_computation.9, metadata={op_name="reduce_window_sum"}
  %fusion.28 = f32[8]{0:T(1024)} fusion(f32[8]{0} %add_bitcast_fusion.8), \
kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(fit_cd_tol)/\
while/body/cd.stats/exp" source_file="cox.py" source_line=199}
  %copy-start.3 = (f32[8]{0}, f32[8]{0}, u32[]) copy-start(f32[8]{0} %p)
  ROOT %add.2 = f32[8]{0} add(f32[8]{0} %fusion.28, f32[8]{0} %p), \
metadata={op_name="jit(fit_cd_tol)/cd.update/add"}
}
"""
STATS = "jit(fit_cd_tol)/while/body/cd.stats/exp"


def test_instructions_map_to_their_op_names():
    names, inherited = scopes.op_names(HLO)
    # the compiler's own instructions (no op_name, or one with no name
    # stack) take their nearest user's; one with no named neighbour
    # (the copy of a parameter, unused) has none and reads unknown
    ran = ("exponential.1", "fusion.28", "reduce-window.60",
           "add_bitcast_fusion.8", "add.2")
    assert {n: names[n] for n in ran} == {
        "exponential.1": STATS, "fusion.28": STATS,
        "reduce-window.60": STATS, "add_bitcast_fusion.8": STATS,
        "add.2": "jit(fit_cd_tol)/cd.update/add"}
    assert {"reduce-window.60", "add_bitcast_fusion.8"} <= inherited
    assert not inherited & {"exponential.1", "fusion.28", "add.2"}
    assert scopes.scope_of(names.get("copy-start.3")) == scopes.UNKNOWN


def test_an_operand_names_what_no_user_names():
    hlo = ("  %a = f32[8]{0} exp(%p), metadata={op_name=\"jit(f)/ssm.ssd/"
           "exp\"}\n  ROOT %copy.1 = f32[8]{0} copy(%a)\n")
    names, inherited = scopes.op_names(hlo)
    assert names["copy.1"] == "jit(f)/ssm.ssd/exp"
    assert inherited == {"copy.1"}


def _ps(events):
    """Events on whole picoseconds as ``tracing.read_events`` gives them:
    in seconds, named by instruction and result shape."""
    return [(f"{n} f32[8]", s * 1e-12, e * 1e-12) for n, s, e in events]


def _window(lo, hi):
    return [(tracing.WINDOW, lo * 1e-12, hi * 1e-12), ("bench.solve", 0, 1)]


def test_credit_gives_self_time_to_the_innermost_scope():
    """A while loop's event spans its body: the loop keeps its own self
    time, under its own op_name's scope, and each body operation goes to
    its own."""
    names = {
        "while.18": "jit(fit_cd_tol)/while/body/while",
        "fusion.28": "jit(fit_cd_tol)/while/body/while/body/closed_call/"
                     "cd.stats/exp",
        "add.2": "jit(fit_cd_tol)/while/body/while/body/cd.update/add",
        "mul.3": "jit(f)/transpose(jvp(ssm.ssd))/mul"}
    evs = [("while.18", 0, 10), ("fusion.28", 1, 5), ("add.2", 5, 6),
           ("fusion.28", 7, 8), ("copy.9", 8, 9), ("mul.3", 12, 13)]
    times = scopes.op_seconds({"/device:TPU:0": _ps(evs)}, _window(0, 20), 1)
    assert times == pytest.approx({"while.18": 3e-12, "fusion.28": 5e-12,
                                   "add.2": 1e-12, "copy.9": 1e-12,
                                   "mul.3": 1e-12})
    got = scopes.credit(times, names)
    assert got == pytest.approx({"cd.stats": 5e-12, "cd.update": 1e-12,
                                 scopes.UNSCOPED: 3e-12,
                                 scopes.UNKNOWN: 1e-12, "ssm.ssd": 1e-12})
    # the credited time is the device's busy time, nothing twice
    busy = sum(e - s for s, e in tracing.union((s, e) for _, s, e in evs))
    assert sum(got.values()) == pytest.approx(busy * 1e-12)


def test_self_times_are_clipped_to_the_window_and_averaged_over_chips():
    dev = {"/device:TPU:0": _ps([("a", 0, 4), ("a", 11, 12)]),
           "/device:TPU:1": _ps([("a", 2, 4)]),
           "/device:TPU:2": _ps([("a", 0, 9)])}
    got = scopes.op_seconds(dev, _window(1, 10), 2)
    assert got == pytest.approx({"a": (3 + 2) / 2 * 1e-12})


def test_breakdown_reports_what_an_inherited_op_name_credited(
        monkeypatch, capsys):
    """The time credited through an inherited op_name is printed per
    scope and by kind of instruction, beside the shares."""
    evs = [("fusion.28", 0, 4), ("reduce-window.60", 4, 6),
           ("add_bitcast_fusion.8", 6, 7), ("add.2", 7, 8)]
    monkeypatch.setattr(tracing, "read_events", lambda path: (
        {"/device:TPU:0": _ps(evs)}, _window(0, 10)))
    ctx = {"trace_path": "trace.xplane.pb", "trace": {"busy_s": 8e-12},
           "driver": None}
    got = scopes.breakdown(ctx, lambda driver: HLO)
    assert got == pytest.approx({"cd.stats": 7e-12, "cd.update": 1e-12})
    line = capsys.readouterr().err
    assert line.startswith("scopes: ")
    assert ("through an inherited op_name {'cd.stats': {'s': 3e-12, "
            "'kinds': {'reduce-window': 2e-12, 'add_bitcast_fusion': "
            "1e-12}}}") in line


def test_breakdown_fails_where_a_busy_device_left_no_trace_file():
    """Device work traced, but no trace file reaches the readers: an
    error, not a metric dropped without a word."""
    ctx = {"trace": {"busy_s": 1.0, "window_s": 1.0}, "driver": None}
    with pytest.raises(RuntimeError, match="trace_path"):
        scopes.breakdown(ctx, lambda driver: HLO)


# -- the scopes in the compiled programs -----------------------------------

def _op_names(hlo_text):
    import re

    return re.findall(r'op_name="([^"]*)"', hlo_text)


def _op_name_scopes(hlo_text):
    found = set()
    for name in _op_names(hlo_text):
        found.update(p for p in scopes.parts(name) if p in scopes.SCOPES)
    return found


def test_fit_program_names_its_scopes(f32):
    import jax.numpy as jnp

    import datagen
    from repro.core import cox, solvers

    size = cells.FIT_SIZE
    x, t, d, _ = datagen.appc(0, size["n"], size["p"], size["k"], 0.9, 0.1,
                              1.0)
    data = cox.prepare(jnp.asarray(x), jnp.asarray(t), jnp.asarray(d))
    text = solvers.fit_cd_tol.lower(data, lam1=1.0, lam2=1.0, max_iters=50,
                                    tol=0.1).compile().as_text()
    assert {"cd.stats", "cd.update", "cd.objective"} <= _op_name_scopes(text)


def test_train_step_names_its_scopes(f32):
    import jax

    from repro.configs.base import TrainConfig
    from repro.models import build_model
    from repro.train.optimizer import init_opt_state
    from repro.train.trainer import TrainState, make_train_step

    spec = harness.load_spec()
    cell = harness.Cell(spec, "mamba2.train")
    cfg = {**cell.config, **cells.TRAIN_SIZE}
    load = {**cell.traffic, **cells.TRAIN_LOAD}
    train = harness.load_module(os.path.join(
        harness.BENCH, "drivers", "train.py"), "bench_driver_train_scopes")
    mcfg = train.model_config(cfg)
    model = build_model(mcfg)
    hp = cfg["training"]
    tcfg = TrainConfig(learning_rate=hp["learning_rate"],
                       warmup_steps=hp["warmup_steps"],
                       total_steps=hp["total_steps"],
                       weight_decay=hp["weight_decay"], beta1=hp["beta1"],
                       beta2=hp["beta2"], grad_clip=hp["grad_clip"],
                       remat=hp["remat"])
    params = jax.eval_shape(
        lambda k: train.make_params(k, cfg, mcfg.vocab_padded),
        jax.random.PRNGKey(0))
    state = jax.eval_shape(
        lambda p: TrainState(params=p, opt=init_opt_state(p)), params)
    batch = train.datagen.survival_tokens(1, 0, load["batch"],
                                          load["seq_len"], cfg["vocab_size"])
    step = jax.jit(make_train_step(model, tcfg, objective="cox"))
    text = step.lower(state, batch).compile().as_text()
    want = {"ssm.in_proj", "ssm.conv", "ssm.ssd", "ssm.gated_norm",
            "ssm.out_proj", "model.embed", "model.norm", "cox.head",
            "optim.adamw"}
    assert want <= _op_name_scopes(text)
    # the backward pass and the recomputation are credited to the
    # forward's scopes
    ssd = [n for n in _op_names(text)
           if scopes.scope_of(n) == "ssm.ssd"]
    assert any(n.split("/")[1].startswith("transpose(") for n in ssd)
    assert any("rematted_computation" in n.split("/") for n in ssd)


# -- the program's spans on the profiler's clock ------------------------------

def test_a_program_span_reaches_the_profiler_trace(f32):
    """A recorded CPU trace holds the program's ``service.step`` span (no
    span sink: the profiler alone turns it on), on the host plane's clock,
    inside the benchmark's annotation around it."""
    from repro.obs import trace

    trace.configure(None)
    cap = tracing.Capture()
    try:
        cap.start()
        with tracing.window(cap):
            with tracing.annotate("bench.solve"):
                with trace.span("service.step"):
                    time.sleep(0.01)
        cap.stop()
        _, host = tracing.read_events(cap.path)
    finally:
        cap.cleanup()
    step = [ev for ev in host if ev[0] == "service.step"]
    assert len(step) == 1
    (_, s, e), = step
    (_, bs, be), = [ev for ev in host if ev[0] == "bench.solve"]
    assert bs <= s and e <= be and e - s >= 0.01


def test_readers_are_silent_without_a_device_plane(f32, tmp_path):
    """On the CPU a traced run's trace has no device plane: the scope
    metrics read nothing and raise nothing."""
    cap = tracing.Capture()
    try:
        cap.start()
        with tracing.window(cap):
            time.sleep(0.01)
        cap.stop()
        ctx = {"trace_path": cap.path, "trace": {"window_s": 0.01},
               "driver": None}
        for name in ("fit_cd.stats_us", "fit_cd.update_us", "train.ssd_ms",
                     "train.proj_ms", "train.adamw_ms"):
            mod = harness.load_module(
                os.path.join(harness.BENCH, "metrics", name + ".py"),
                "bench_metric_silent_" + name.replace(".", "_"))
            assert mod.read(ctx) is None
    finally:
        cap.cleanup()
